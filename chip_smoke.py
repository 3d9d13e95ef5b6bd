#!/usr/bin/env python3
"""Chip smoke: drive the system's main paths once on a TPU and check them.

    python chip_smoke.py              # one chip: phase A, then phase B
    python chip_smoke.py --chips 4    # four chips: sharded staging only

Phase A stages the paper's kernels (``stage_spmv``/``stage_spmm``) on two
seeded 10k x 10k matrices from ``synthesize_paper``, once with
``backend="auto"`` (which must resolve to the compiled Pallas kernels) and
once with ``backend="autotune"`` against an empty plan cache, so that every
candidate is measured on the chip.  Phase B builds ``ServeEngine`` for
llama3-8b-sable at its published widths (bf16 weights from a seed) and
serves 4 requests through ``engine.serve``.  ``--chips 4`` runs only the
sharded staging path (``make_staging_mesh(4)`` and ``(2, 2)``) against the
single-device staged result and the NumPy reference.

Every result is checked against a reference; any failure exits non-zero.
The times printed are a smoke's, not a benchmark's.  With no TPU the script
exits non-zero before any phase and prints no result.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the autotune phases start from this empty plan cache, so that tuning
# measures on the chip instead of loading plans from an earlier run
PLAN_DIR = os.path.join(ROOT, ".smoke_plans")

# <row_splits, col_splits, num_blocks, u|nu> of the paper's Tables I-IV
PAPER_MATRICES = ((100, 100, 500, True), (50, 50, 500, False))
PAPER_SIZE = 10_000
SPMM_COLS = 128
SEED = 0

# An f32 matmul on a TPU at default precision may run one bf16 pass: a and
# x are each rounded to 8 significant bits, so every product is off by at
# most 2 * 2^-8 of its magnitude and the sum by at most 2^-7 * (|A| @ |x|)
# (f32 accumulation adds ~1e-7).  The check allows 1e-2 * (|A| @ |x|).
REL_TOL = 1e-2

SERVE_PROMPT_LEN = 128
SERVE_NEW_TOKENS = 16
SERVE_REQUESTS = 4


def log(*args) -> None:
    print(*args, flush=True)


class SmokeFailure(Exception):
    pass


def require(ok, message: str) -> None:
    """A check that stays under ``python -O``, unlike ``assert``."""
    if not ok:
        raise SmokeFailure(message)


def require_no_candidate_failures() -> None:
    from repro.core.autotune import autotune_stats

    failures = autotune_stats()["candidate_failures"]
    require(failures == 0, f"{failures} tuning candidates failed")


def fresh_plan_cache(path: str) -> None:
    """Point the process-wide plan cache at ``path``, emptied first."""
    from repro.core.cache import PlanCache, set_default_cache

    shutil.rmtree(path, ignore_errors=True)
    set_default_cache(PlanCache(path))


def paper_matrices(size: int = PAPER_SIZE, specs=PAPER_MATRICES, seed: int = SEED):
    from repro.core.vbr import synthesize_paper

    out = []
    for i, (r, c, nb, uniform) in enumerate(specs):
        name = f"<{r},{c},{nb},{'u' if uniform else 'nu'}>"
        v = synthesize_paper(r, c, nb, uniform=uniform, seed=seed + i,
                             rows=size, cols=size)
        out.append((name, v))
    return out


def _operands(v, n_cols: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    k = v.shape[1]
    return (rng.standard_normal(k).astype(np.float32),
            rng.standard_normal((k, n_cols)).astype(np.float32))


def _references(v, x, X):
    """float64 NumPy reference products and the |A| @ |x| error scale."""
    import numpy as np

    dense = v.to_dense().astype(np.float64)
    absd = np.abs(dense)
    return {
        "spmv": (dense @ x.astype(np.float64), absd @ np.abs(x).astype(np.float64)),
        "spmm": (dense @ X.astype(np.float64), absd @ np.abs(X).astype(np.float64)),
    }


def check_close(label: str, got, ref_scale) -> float:
    """Largest |got - ref| / (|A| @ |x|); raises past ``REL_TOL``."""
    import numpy as np

    ref, scale = ref_scale
    got = np.asarray(got, np.float64)
    require(got.shape == ref.shape, f"{label}: shape {got.shape} != {ref.shape}")
    require(np.all(np.isfinite(got)), f"{label}: non-finite output")
    err = np.abs(got - ref) / np.maximum(scale, 1e-30)
    worst = float(err.max()) if err.size else 0.0
    log(f"  {label}: max |y - ref| = {float(np.abs(got - ref).max()):.3e}, "
        f"max |y - ref| / (|A||x|) = {worst:.3e} (tol {REL_TOL:g})")
    require(worst <= REL_TOL, f"{label}: error {worst:.3e} > {REL_TOL:g}")
    return worst


def timed_calls(fn, *args, iters: int = 5):
    """(first call incl. compile, median steady call) in seconds."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return out, first, float(np.median(ts))


def _fmt_timings(timings: dict) -> str:
    return ", ".join(f"{k}={v * 1e3:.3f}ms" for k, v in sorted(timings.items()))


# ---------------------------------------------------------------------- #
# phase A: the paper's staged kernels
# ---------------------------------------------------------------------- #
def phase_kernels(matrices, n_cols: int = SPMM_COLS, *, on_tpu: bool = True,
                  seed: int = SEED) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.autotune import autotune, autotune_stats, candidate_options
    from repro.core.staging import StagingOptions, clear_cache, stage_spmm, stage_spmv

    tuned = StagingOptions(backend="autotune")
    for i, (name, v) in enumerate(matrices):
        log(f"[A] {name}: shape {v.shape}, {v.num_blocks} blocks, "
            f"{v.stored_nnz} stored values")
        x, X = _operands(v, n_cols, seed + i)
        refs = _references(v, x, X)
        val, xd, Xd = jnp.asarray(v.val), jnp.asarray(x), jnp.asarray(X)
        stages = (
            ("spmv", lambda o: stage_spmv(v, o), xd, None),
            ("spmm", lambda o: stage_spmm(v, n_cols, o), Xd, n_cols),
        )
        for kind, stage, operand, cols in stages:
            kern = stage(StagingOptions())
            log(f"  {kind} auto -> backend {kern.backend}")
            if on_tpu:
                require(kern.backend == "pallas",
                        f"{kind}: backend='auto' resolved to {kern.backend!r} on a TPU")
            y, first, steady = timed_calls(kern, val, operand)
            log(f"  {kind} auto: first call {first:.3f}s, steady "
                f"{steady * 1e3:.3f}ms (smoke timing, not a benchmark)")
            check_close(f"{kind} auto", y, refs[kind])

            before = autotune_stats()["benchmarks"]
            t0 = time.perf_counter()
            kern = stage(tuned)
            tune_s = time.perf_counter() - t0
            require(autotune_stats()["benchmarks"] > before,
                    f"{kind}: autotune measured nothing (plan cache not empty?)")
            plan = autotune(v, kind, cols)  # the stored plan: a cache hit
            log(f"  {kind} autotune: {tune_s:.3f}s, winner {plan.options.backend}"
                f"{list(plan.options.tile) if plan.options.backend == 'pallas' else ''}"
                f", timings: {_fmt_timings(plan.timings)}")
            labels = [lbl for lbl, _ in candidate_options(v)]
            missing = [lbl for lbl in labels if lbl not in plan.timings]
            require(not missing, f"{kind}: candidates without a timing: {missing}")
            if on_tpu:
                require(any(lbl.startswith("pallas[") for lbl in labels),
                        f"{kind}: no pallas candidate among {labels}")
            y, first, steady = timed_calls(kern, val, operand)
            log(f"  {kind} autotuned: first call {first:.3f}s, steady "
                f"{steady * 1e3:.3f}ms (smoke timing, not a benchmark)")
            check_close(f"{kind} autotuned", y, refs[kind])
    require_no_candidate_failures()
    # the staged programs keep their gather tables as device constants;
    # drop them before phase B loads 7.6 GB of weights
    clear_cache()
    jax.clear_caches()


# ---------------------------------------------------------------------- #
# phase B: the sparse-FFN server at full width
# ---------------------------------------------------------------------- #
def smoke_model_config():
    """llama3-8b-sable at its published widths with bf16 weights: f32
    weights (~15.2 GB) do not fit a 16 GB chip next to anything else."""
    from repro.configs import llama3_8b

    return dataclasses.replace(llama3_8b.full_sable(), param_dtype="bfloat16")


def phase_server(cfg, *, prompt_len: int = SERVE_PROMPT_LEN,
                 new_tokens: int = SERVE_NEW_TOKENS,
                 n_requests: int = SERVE_REQUESTS, on_tpu: bool = True,
                 seed: int = SEED) -> None:
    import jax
    import numpy as np

    from repro.core.cache import default_cache, plan_key
    from repro.models.transformer import init_params
    from repro.serve.engine import ServeEngine
    from repro.sparse.linear import pattern_hash

    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(seed)))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    log(f"[B] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
        f"{cfg.d_ff}, {n_params / 1e6:.1f}M {cfg.param_dtype} parameters "
        f"initialised in {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    engine = ServeEngine(cfg, params, max_len=prompt_len + new_tokens)
    log(f"  engine warmup {time.perf_counter() - t0:.3f}s, "
        f"warmup_stats {engine.warmup_stats}")
    log(f"  sparse_plans {engine.sparse_plans}")
    require(len(engine.patterns) == 2, "expected the two FFN patterns")
    device = jax.default_backend()
    for name, p in zip(("in", "out"), engine.patterns):
        plan = default_cache().load_plan(plan_key("linear", pattern_hash(p), device))
        require(plan is not None, f"no stored plan for the {name} pattern")
        log(f"  plan {name} ({p.d_in}x{p.d_out}, {p.n_tiles} tiles): winner "
            f"{plan.options.backend}, timings: {_fmt_timings(plan.timings)}")
        if on_tpu:
            require({"grouped", "pallas"} <= set(plan.timings),
                    f"{name} pattern: not every strategy was measured: {plan.timings}")

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(n_requests, prompt_len),
                           dtype=np.int32)
    reqs = [{"prompt": p, "max_new_tokens": new_tokens, "rid": f"r{i}"}
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    results, sched = engine.serve(reqs, page_size=16, max_batch=4,
                                  record_logits=True)
    log(f"  serve {n_requests} requests (first, incl. compile): "
        f"{time.perf_counter() - t0:.3f}s, {sched.stats['steps']} steps")
    for i in range(n_requests):
        rid = f"r{i}"
        toks = results[rid]["tokens"]
        require(results[rid]["state"] == "FINISHED",
                f"{rid}: state {results[rid]['state']}")
        require(len(toks) == prompt_len + new_tokens, f"{rid}: {len(toks)} tokens")
        rows = np.stack(sched.requests[rid].logits)
        require(rows.shape == (new_tokens, cfg.vocab_size),
                f"{rid}: logits {rows.shape}")
        require(np.all(np.isfinite(rows)), f"{rid}: non-finite logits")

    # steady: the same scheduler (already compiled) serves 4 more requests
    for i, p in enumerate(prompts):
        sched.submit(p, new_tokens, rid=f"s{i}")
    t0 = time.perf_counter()
    sched.run()
    steady = time.perf_counter() - t0
    log(f"  serve {n_requests} more requests on the compiled scheduler: "
        f"{steady:.3f}s, {n_requests * new_tokens / steady:.1f} tokens/s "
        "(smoke timing, not a benchmark)")

    agree_all, margins, first_ok = [], [], []
    for i, p in enumerate(prompts):
        out, _ = engine.generate(jax.numpy.asarray(p[None]), new_tokens)
        ref = np.asarray(out[0, prompt_len:])
        got = results[f"r{i}"]["tokens"][prompt_len:]
        agree_all.append(int((ref == got).sum()))
        first_ok.append(bool(got[0] == ref[0]))
        top2 = np.sort(sched.requests[f"r{i}"].logits[0])[-2:]
        margins.append(float(top2[1] - top2[0]))
    log(f"  tokens agreeing with engine.generate, per request (of "
        f"{new_tokens}): {agree_all}; first-token logit margins (top1 - top2): "
        f"{[f'{m:.4f}' for m in margins]}")
    require(all(first_ok), f"first greedy token differs from generate's: {first_ok}")
    require_no_candidate_failures()


# ---------------------------------------------------------------------- #
# --chips 4: sharded staging against the single-device staged result
# ---------------------------------------------------------------------- #
def phase_sharded(matrices, n_cols: int = SPMM_COLS, *, n_devices: int = 4,
                  seed: int = SEED) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.staging import stage_spmm, stage_spmv
    from repro.launch.mesh import make_staging_mesh

    half = n_devices // 2
    meshes = ((f"1-D ({n_devices},)", make_staging_mesh(n_devices)),
              (f"2-D ({half}, 2)", make_staging_mesh((half, 2))))
    for i, (name, v) in enumerate(matrices):
        log(f"[S] {name}: shape {v.shape}, {v.num_blocks} blocks")
        x, X = _operands(v, n_cols, seed + i)
        refs = _references(v, x, X)
        val, xd, Xd = jnp.asarray(v.val), jnp.asarray(x), jnp.asarray(X)
        single = {"spmv": np.asarray(stage_spmv(v)(val, xd)),
                  "spmm": np.asarray(stage_spmm(v, n_cols)(val, Xd))}
        check_close("spmv single device", single["spmv"], refs["spmv"])
        check_close("spmm single device", single["spmm"], refs["spmm"])
        for mesh_name, mesh in meshes:
            for kind, operand in (("spmv", xd), ("spmm", Xd)):
                if kind == "spmv":
                    kern = stage_spmv(v, mesh=mesh)
                else:
                    kern = stage_spmm(v, n_cols, mesh=mesh)
                y, first, steady = timed_calls(kern, val, operand)
                devs = sorted(d.id for d in y.sharding.device_set)
                nnz = [s.nnz for s in kern.plan.shards]
                backends = sorted({k.backend for k in kern.kernels})
                log(f"  {kind} {mesh_name}: shard backends {backends}, imbalance "
                    f"{kern.imbalance():.4f}, shard nnz {nnz}, output on devices "
                    f"{devs}, first call "
                    f"{first:.3f}s, steady {steady * 1e3:.3f}ms "
                    "(smoke timing, not a benchmark)")
                require(len(devs) == mesh.devices.size,
                        f"{kind} {mesh_name}: output only on devices {devs}")
                require(all(n > 0 for n in nnz), f"a shard holds no work: {nnz}")
                diff = float(np.abs(np.asarray(y) - single[kind]).max())
                log(f"  {kind} {mesh_name}: max |sharded - single device| = "
                    f"{diff:.3e}")
                check_close(f"{kind} {mesh_name}", y, refs[kind])
    require_no_candidate_failures()


# ---------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-staging path on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    log(f"jax {jax.__version__}, devices: {devices}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compilation cache: {enable_compile_cache()}")
    fresh_plan_cache(PLAN_DIR)

    t0 = time.perf_counter()
    matrices = paper_matrices()
    log(f"matrices generated in {time.perf_counter() - t0:.3f}s")
    if args.chips == 4:
        phases = [("sharded staging", lambda: phase_sharded(matrices))]
    else:
        phases = [
            ("A staged kernels", lambda: phase_kernels(matrices)),
            ("B sparse-FFN server", lambda: phase_server(smoke_model_config())),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.3f}s")
        else:
            log(f"phase {name}: passed in {time.perf_counter() - t0:.3f}s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
