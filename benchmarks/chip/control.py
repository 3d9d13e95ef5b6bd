#!/usr/bin/env python3
"""Readings that set a cell's limits: the program and its control, on seeds.

    python3 benchmarks/chip/control.py --workload <name> --seeds 11,12,13 \\
        --seconds <s>

For every seed it runs the cell's driver as a benchmark run does, once for
the program and once for the control: the configuration's reference put in
the program's place, computed in the next precision below the one the
configuration states (``control_dtype``).  A served model's control does
not decode; the serving driver reads, at each position of the program's own
prompts and served tokens, the gap of the token that the control puts
first.  Each number compared is printed for the program and for the control,
and the last line is a JSON summary: the largest program reading (the lower
end of a limit) and the smallest control reading (the upper end).  With
``--fault a,b`` it also reads the cell's number with each named fault of
``faults.py`` planted in the timed path, on the same seeds.

The benchmark's own runs never run this.  It needs a TPU, as run.py does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402
import run as runmod  # noqa: E402


def _drive(cell, seed, seconds, control=False, fault=None):
    import tracecalc

    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                          tracer=tracecalc.Tracer("", False),
                          t_process=time.perf_counter(), fault=fault, control=control)
    return cell.driver().run(ctx)


def readings(cell, seeds, seconds, control_only=False):
    """{"program": [...], "control": [...]} of the cell's compared number."""
    (check,) = cell.limits
    out = {"program": [], "control": []}
    for seed in seeds:
        res = _drive(cell, seed, seconds, control=True)
        control = res.checks[check][0]
        # a served model's control run reads the program's gap too
        program = res.counters.get("program_gap")
        if program is None and not control_only:
            program = _drive(cell, seed, seconds).checks[check][0]
        out["control"].append(control)
        if program is not None:
            out["program"].append(program)
        harness.log(f"seed {seed}: program {check} {program}, control {control}")
    return out


def fault_readings(cell, seeds, seconds, name):
    """The cell's compared number with the fault ``name`` planted, per seed."""
    import faults

    (check,) = cell.limits
    plant = faults.BY_DRIVER[cell.traffic["driver"]][name]
    out = []
    for seed in seeds:
        res = _drive(cell, seed, seconds, fault=plant)
        value = res.checks[check][0]
        out.append(value)
        harness.log(f"seed {seed}: fault {name} {check} {value} "
                    f"(limit {cell.limits[check]}, correct "
                    f"{harness.check_passes(value, cell.limits[check])})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-only", action="store_true")
    ap.add_argument("--fault-only", action="store_true", help="read the faults alone")
    ap.add_argument("--fault", default="",
                    help="comma-separated faults (faults.py) read on the same seeds")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    runmod._configure_caches()
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"control.py: needs a TPU, JAX found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    seeds = [int(s) for s in args.seeds.split(",")]
    r = readings(cell, seeds, args.seconds, args.control_only) if not args.fault_only \
        else {"program": [], "control": []}
    (check,) = cell.limits
    faults = {name: fault_readings(cell, seeds, args.seconds, name)
              for name in args.fault.split(",") if name}
    summary = {"workload": cell.name, "check": check, "limit": cell.limits[check],
               "seeds": seeds, **r,
               "lower": max(r["program"]) if r["program"] else None,
               "upper": min(r["control"]) if r["control"] else None,
               "faults": faults}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
