"""``moe_share.serve``: the serving programs' device op time under the
``moe.route``, ``moe.experts`` and ``moe.shared`` scopes (router, routed
experts with their dispatch and combine, shared experts), over the serving
programs' whole op time, in % (``scopecalc``).  Logs the time by scope and
by program (decode lane step or prefill).  None where no op carries a
``moe.`` scope."""
import harness
import scopecalc
import spancalc

SCOPES = ("moe.route", "moe.experts", "moe.shared")


def read(run):
    times = scopecalc.scope_times(spancalc.window_records(run.outcome.trace_path), SCOPES)
    if times:
        harness.log("moe scopes (device s): " + scopecalc.format_times(times))
    return scopecalc.share(times)
