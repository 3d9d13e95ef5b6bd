"""``staged_roofline``: the staged call's share of its roofline, in %.

The least time for the work one call needs (``workcount.staged_call``,
bound by HBM bytes or by peak operations, whichever is larger) times the
calls traced, over the device time of the programs those calls ran.  The
benchmark's own programs (named ``jit_bench_*``) are left out of the
denominator."""
import harness
import workcount


def read(run):
    tr = run.trace
    if tr is None:
        return None
    calls = tr["spans"].get("staged.call", 0)
    device_s = sum(m["seconds"] for name, m in tr["modules"].items()
                   if not name.startswith("jit_bench_"))
    if calls == 0 or device_s <= 0:
        return None
    c = run.outcome.counters
    work = workcount.staged_call(c["structure"], c["n_cols"])
    need_s, bound = workcount.roofline_seconds(work, run.peaks)
    harness.log(f"staged_roofline: {work['bytes']} bytes and {work['flops']} flops per call, "
                f"least {need_s * 1e3:.6f} ms ({bound} bound); {calls} calls took "
                f"{device_s:.6f} s on the device ({device_s / calls * 1e3:.6f} ms each)")
    return 100.0 * need_s * calls / device_s
