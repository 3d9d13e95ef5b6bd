"""``mla_share.serve``: the serving programs' device op time under the
``mla.attend`` scope (latent attention's score and value contractions,
absorbed at decode and materialised at prefill), over the serving
programs' whole op time, in % (``scopecalc``).  Logs the time by program
(decode lane step or prefill).  None where no op carries the scope."""
import harness
import scopecalc
import spancalc

SCOPES = ("mla.attend",)


def read(run):
    times = scopecalc.scope_times(spancalc.window_records(run.outcome.trace_path), SCOPES)
    if times:
        harness.log("mla scopes (device s): " + scopecalc.format_times(times))
    return scopecalc.share(times)
