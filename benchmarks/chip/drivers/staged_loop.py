"""Solver-loop driver for a staged sparse kernel: power iteration.

Traffic parameters (``workloads/<traffic>.json``):

* ``kind``: ``spmv`` or ``spmm``; ``n_cols``: the operand's columns (SpMM);
* ``backend``: the ``StagingOptions.backend`` the cell stages with;
* ``check_samples``: how many calls of the window, drawn from the seed, are
  compared with the reference besides the first and the last;
* ``in_flight``: how many calls may be queued on the device before the loop
  waits for the oldest, so that the device always holds the next call and
  the host's own pauses do not reach it;
* ``trace_seconds``: the longest window a traced run records.

Set-up builds the matrix's structure from the configuration, its values
and the first operand on the device from ``--seed``, stages the kernel
through the program's entry (``stage_spmv`` / ``stage_spmm``; the plan cache
in the checkout means only a cell's first run tunes), and runs two calls,
which compile.  The window then queues calls of the staged kernel back to
back, each call's operand the previous result rescaled on the device (per
column for SpMM), until ``--seconds`` have passed, and closes once every
queued call has completed.  Nothing on the host reads a result inside the
window, and every operand stays on the device.

``call_ms`` is the window's length over the calls completed in it.
"""
from __future__ import annotations

import collections
import time

import numpy as np

import harness
from tracecalc import span


def bench_rescale(y):
    import jax.numpy as jnp

    return y / jnp.linalg.norm(y, axis=0, keepdims=True)


def stage(kind: str, vbr, n_cols, backend: str):
    """The program's staging entry, as its README tells users to call it."""
    from repro.core.staging import StagingOptions, stage_spmm, stage_spmv

    opts = StagingOptions(backend=backend)
    return stage_spmv(vbr, opts) if kind == "spmv" else stage_spmm(vbr, n_cols, opts)


def run(ctx: harness.Context) -> harness.Outcome:
    """With ``ctx.control`` the reference, in the configuration's lower
    precision, takes the staged kernel's place: the control runs."""
    import jax

    from repro.core.vbr import VBR

    cell, traffic = ctx.cell, ctx.cell.traffic
    ref_mod = cell.reference()
    kind, n_cols = traffic["kind"], traffic.get("n_cols")

    st = ref_mod.make_structure(cell.config)
    val = ref_mod.make_values(st, harness.jax_key(ctx.seed, 1))
    x = ref_mod.make_operand(st, n_cols, harness.jax_key(ctx.seed, 2))
    host_val = np.asarray(val)
    vbr = VBR(shape=st.shape, rpntr=st.rpntr, cpntr=st.cpntr, bindx=st.bindx,
              bpntrb=st.bpntrb, bpntre=st.bpntre, indx=st.indx, val=host_val)
    harness.log(f"matrix: shape {st.shape}, {st.num_blocks} blocks, "
                f"{st.stored_nnz} stored values ({st.stored_nnz * 4} bytes f32)")

    t0 = time.perf_counter()
    if ctx.control:
        kern = ref_mod.control_kernel(st, cell.config["control_dtype"][kind])
    else:
        kern = stage(kind, vbr, n_cols, traffic["backend"])
        _log_plan(kind, vbr, n_cols, kern)
    if ctx.fault is not None:
        kern = ctx.fault(kern)
    rescale = jax.jit(bench_rescale)
    for _ in range(2):  # the first call compiles; the second is steady
        t1 = time.perf_counter()
        y = jax.block_until_ready(kern(val, x))
        call_s = time.perf_counter() - t1
    x = jax.block_until_ready(rescale(y))
    harness.log(f"staging and warm-up {time.perf_counter() - t0:.3f} s, "
                f"warm call {call_s * 1e3:.3f} ms")

    rng = np.random.default_rng(harness.seed_words(ctx.seed, 4))
    n_est = max(2, int(ctx.seconds / max(call_s, 1e-6)))
    picks = set(rng.choice(np.arange(1, n_est), size=min(n_est - 1, int(traffic["check_samples"])),
                           replace=False).tolist())
    picks.add(0)
    kept, last = [], None
    n = 0
    queued = collections.deque()
    depth = int(traffic["in_flight"])
    setup_s = time.perf_counter() - ctx.t_process
    with ctx.tracer.window() as tracer:
        t_start = time.perf_counter()
        while True:
            with span("staged.call"):
                y = kern(val, x)
            if n in picks:
                kept.append((x, y))
            last = (x, y)
            x = rescale(y)
            queued.append(y)
            n += 1
            if len(queued) > depth:
                with span("staged.wait"):
                    jax.block_until_ready(queued.popleft())
            if time.perf_counter() - t_start >= ctx.seconds:
                break
        with span("staged.wait"):
            jax.block_until_ready(x)
        t_end = time.perf_counter()
    peak = harness.memory_peak_bytes()
    window_s = t_end - t_start
    call_ms = window_s * 1e3 / n
    harness.log(f"window {window_s:.6f} s, {n} calls, call_ms {call_ms:.6f}, "
                f"setup_s {setup_s:.6f}")

    if last is not None and all(last[1] is not k[1] for k in kept):
        kept.append(last)
    ref = ref_mod.Reference(st, host_val)
    t_ref = time.perf_counter()
    errs = [ref.rel_err(np.asarray(xk), np.asarray(yk)) for xk, yk in kept]
    worst = max(errs)
    nonfinite = sum(1 for e in errs if not np.isfinite(e))
    harness.log(f"compared {len(kept)} calls (of {n}) with the float64 reference in "
                f"{time.perf_counter() - t_ref:.3f} s; rel errors {[f'{e:.3e}' for e in errs]}")

    return harness.Outcome(
        end_to_end={"call_ms": call_ms, "setup_s": setup_s},
        checks={"max_rel_err": (worst, cell.limits["max_rel_err"])},
        attempted=n,
        failed=nonfinite,
        counters={"calls": n, "window_s": window_s, "kind": kind, "n_cols": n_cols,
                  "structure": st},
        memory_peak_bytes=peak,
        trace_path=tracer.path,
    )


def _log_plan(kind, vbr, n_cols, kern) -> None:
    from repro.core.autotune import autotune, autotune_stats

    plan = autotune(vbr, kind, n_cols)  # the stored plan: a cache hit
    times = ", ".join(f"{k}={v * 1e3:.3f}ms" for k, v in sorted(plan.timings.items()))
    harness.log(f"plan: winner {plan.options.backend} tile {list(plan.options.tile)} "
                f"(source {plan.source}); candidates {times}; autotune {autotune_stats()}; "
                f"stage0 {getattr(kern, 'stage0_time', float('nan')):.3f} s")
