#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (see ``harness.py`` for where
its files live), sets up, warms up every shape the window uses, measures
for ``--seconds`` seconds, checks what the timed path produced against the
cell's plain reference, and prints one JSON line as the last line of
standard output.  ``--trace 1`` also records a profiler trace of the window
and reports the cell's per-layer metrics instead of its end-to-end ones.

With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.  Compiled programs, tuned plans and traces stay in
``benchmarks/chip/.cache`` and ``.traces`` inside the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402


def _configure_caches() -> None:
    """Every cache inside the checkout at a fixed path, before JAX loads."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(harness.CACHE_DIR, "jax")
    os.environ["REPRO_CACHE_DIR"] = os.path.join(harness.CACHE_DIR, "plans")
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # room for every program of every cell: a staged program of the paper's
    # matrix carries its gather tables, ~150 MB, and a smaller limit evicts it
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    _configure_caches()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    peaks = harness.peaks_for(devices[0].device_kind)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))

    import tracecalc

    tracer = tracecalc.Tracer(os.path.join(harness.TRACE_DIR, cell.name), bool(args.trace))
    # a traced run records a window of at most the traffic's trace_seconds
    seconds = min(args.seconds, float(cell.traffic["trace_seconds"])) if args.trace \
        else args.seconds
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=seconds,
                          trace=bool(args.trace), tracer=tracer, t_process=T_PROCESS)
    harness.log(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}, "
                f"seed {args.seed}, {seconds} s, trace {args.trace}; jax {jax.__version__}, "
                f"devices {devices}")
    out = cell.driver().run(ctx)
    line = harness.result(ctx, out, peaks)
    if args.trace and "busy_s" not in line["device"]:
        print("run.py: the traced window holds no device operation", file=sys.stderr)
        return 1
    harness.print_checks(line)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
