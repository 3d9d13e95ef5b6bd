"""``staged_pack_share``: the staged programs' device op time under the
``sable.pack`` scope (the value gathers into blocks or tiles), over their
whole op time, in % (``spancalc.pack_share``).  The benchmark's own
programs (``jit_bench_*``) are left out, as ``staged_roofline`` does."""
import harness
import spancalc


def read(run):
    recs = spancalc.window_records(run.outcome.trace_path)
    if recs:
        t = spancalc.scope_time(recs)
        harness.log("staged scopes (device s): "
                    + ", ".join(f"{k}: {v / 1e9:.6f}" for k, v in t.items()))
    return spancalc.pack_share(recs)
