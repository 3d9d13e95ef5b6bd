"""``compiles.window.serve``: programs compiled or loaded from the compile
cache inside the traced window: JAX's ``lower_sharding_computation`` events, one
for each new program whichever way it is made (``spancalc.compiles``).
Set-up warms every shape, so 0 is the target."""
import spancalc


def read(run):
    return spancalc.compiles(spancalc.window_records(run.outcome.trace_path))
