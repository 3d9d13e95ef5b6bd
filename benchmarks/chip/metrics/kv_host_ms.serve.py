"""``kv_host_ms.serve``: host time of the paged KV path a decode step, in ms.

The program's ``kv.gather``, ``kv.upload``, ``kv.append`` and ``kv.write``
spans summed over the traced window, over the ``serve.decode`` spans in it
(``spancalc.kv_host_ms``).  Also logs how much of the benchmark's
``scheduler.step`` time and of the device's idle time the program's spans
explain, by innermost span."""
import harness
import spancalc


def read(run):
    recs = spancalc.window_records(run.outcome.trace_path)
    cov = spancalc.coverage(recs)
    if cov is not None:
        for line in spancalc.format_coverage(cov):
            harness.log(line)
    return spancalc.kv_host_ms(recs)
