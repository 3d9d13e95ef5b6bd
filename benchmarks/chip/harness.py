"""The data-driven part of the benchmark: find a cell's files by name, run
its driver, reduce what it measured to the metrics ``BENCHMARK.json``
lists, and print the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under this directory:

* ``BENCHMARK.json`` (at the root of the checkout) names the cells;
* a configuration is the JSON file that ``BENCHMARK.json`` names, and its
  ``reference`` key names its plain reference under ``references/``;
* a traffic mix is ``workloads/<traffic>.json``; its ``driver`` key names
  ``drivers/<driver>.py``, the one generator of that kind of load;
* the limits that decide ``correct`` for a cell are
  ``limits/<workload>.json``;
* a per-layer metric is ``metrics/<metric>.py`` with ``read(run)``, which
  returns a number or None when it finds nothing to read.

A served model's reference owns everything that belongs to its
architecture; the serving driver (``drivers/closed_loop.py``) knows none of
it and reaches the reference through four functions:

* ``program_config(cfg) -> ModelConfig``: the program's config, built from
  the file's ``program`` entry (``module:function`` plus ``replace``) and
  checked against the file's published sizes; raises on any departure;
* ``make_params(cfg, shapes, key)``: weights for every leaf of ``shapes``
  (the program's parameter tree as ShapeDtypeStructs), drawn from ``key``
  on the device;
* ``scores(params, cfg, tokens, targets, control)``: the reference's best
  logit, the logits of the ``targets`` rows and its argmax at every
  position of ``tokens``; with ``control``, computed in the configuration's
  lower precision;
* ``work(cfg) -> (per_token, per_key, per_logits_row)``: the operations
  that ``mfu.serve`` counts, from the configuration alone, with no padding
  or tiles that the mathematics does not need.

A new architecture then brings a configuration file, a reference that keeps
this contract, and its traffic, limits and metric files, and edits none.

A driver's ``run(ctx)`` returns a :class:`Outcome`.  The harness never
imports the program under test; the drivers and references do.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
TRACE_DIR = os.path.join(BENCH_DIR, ".traces")


def log(*args) -> None:
    print(*args, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a file by path (metric files carry dots in their names)."""
    name = name or "chipbench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    """Published peaks of ``kind`` (``jax.Device.device_kind``); a device
    that is not in the table is an error, never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def seed_words(seed: int, n: int = 2) -> list[int]:
    """``n`` 32-bit words derived from a seed of any size."""
    import numpy as np

    return [int(w) for w in np.random.SeedSequence(int(seed)).generate_state(n)]


def jax_key(seed: int, salt: int = 0):
    """A JAX PRNG key from a seed of any size (``--seed`` exceeds 32 bits)."""
    import jax
    import numpy as np

    words = seed_words(int(seed) * 1000003 + salt, 2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32), impl="threefry2x32")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` in ``BENCHMARK.json`` with its files."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list  # BENCHMARK.json entries that this cell reports
    per_layer: list

    def reference(self):
        return load_module(os.path.join(BENCH_DIR, self.config["reference"]))

    def driver(self):
        return load_module(os.path.join(BENCH_DIR, "drivers",
                                        self.traffic["driver"] + ".py"))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(os.path.join(ROOT, cfg_entry["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(BENCH_DIR, "workloads", w["traffic"] + ".json")),
        limits=load_json(os.path.join(BENCH_DIR, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and a tracer."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    tracer: Any
    t_process: float  # perf_counter() when the process began its work
    fault: Optional[Callable] = None  # tests break the timed path with it
    control: bool = False  # control runs: the reference in a lower precision


@dataclasses.dataclass
class Outcome:
    """What a driver measured.  ``end_to_end`` maps metric names to values
    (host clock); ``checks`` maps each compared number to (value, limit);
    ``counters`` feeds the per-layer readers."""

    end_to_end: dict
    checks: dict
    attempted: int
    failed: int
    counters: dict
    memory_peak_bytes: Optional[int]
    trace_path: Optional[str] = None


def check_passes(value, limit) -> bool:
    return value is not None and math.isfinite(value) and value <= limit


def _num(v):
    """JSON has no NaN or infinity: a non-finite reading prints as null."""
    if v is None:
        return None
    v = float(v)
    return v if math.isfinite(v) else None


def device_info(memory_peak_bytes) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak_bytes}


def memory_peak_bytes() -> Optional[int]:
    """Peak device memory on the fullest chip, as the backend reports it."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class RunRecord:
    """What a per-layer reader sees."""

    cell: Cell
    outcome: Outcome
    trace: Optional[dict]  # tracecalc.reduce() of the traced window
    peaks: dict


def result(ctx: Context, out: Outcome, peaks: Optional[dict]) -> dict:
    """Assemble the result line (a dict) and print the earlier lines."""
    import tracecalc

    cell = ctx.cell
    correct = all(check_passes(v, lim) for v, lim in out.checks.values())
    device = device_info(out.memory_peak_bytes)
    line: dict = {"correct": bool(correct), "attempted": int(out.attempted),
                  "failed": int(out.failed)}
    if not ctx.trace:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in out.end_to_end:
                raise KeyError(f"driver {cell.traffic['driver']} gave no {m['name']}")
            metrics[m["name"]] = {"value": _num(out.end_to_end[m["name"]]), "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device
    else:
        red = None
        if out.trace_path:
            events = tracecalc.load_xplane(out.trace_path)
            for s in tracecalc.summarize_planes(events):
                log(f"trace: {s}")
            red = tracecalc.reduce(events, ctx.cell.traffic.get("spans", ()))
        if red is not None:
            log(f"trace: window {red['window_s']:.6f} s, busy {red['busy_s']:.6f} s on "
                f"{red['devices']} device(s), spans {red['spans']}")
            log(f"trace: modules {json.dumps(red['modules'])}")
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
        rec = RunRecord(cell=cell, outcome=out, trace=red, peaks=peaks or {})
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH_DIR, "metrics", m["name"] + ".py"))
            v = reader.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": _num(v), "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device
        if red is not None:
            line["breakdown"] = tracecalc.breakdown(red)
    line["checks"] = {k: {"value": _num(v), "limit": lim} for k, (v, lim) in out.checks.items()}
    return line


def print_checks(line: dict) -> None:
    """The numbers compared, beside their limits: the last lines on stderr."""
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(f"correct: {str(line['correct']).lower()}", file=sys.stderr, flush=True)
