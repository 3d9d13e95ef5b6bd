"""``kv_h2d_mb.serve``: KV bytes put on the device a decode step, in 10^6 B.

The ``bytes_h2d`` of the program's ``kv.upload`` spans in the traced
window, over the ``serve.decode`` spans in it (``spancalc.kv_h2d_mb``)."""
import spancalc


def read(run):
    return spancalc.kv_h2d_mb(spancalc.window_records(run.outcome.trace_path))
