"""Synthetic serving traffic through the continuous-batching scheduler.

Replays Poisson and bursty arrival processes with mixed prompt/generation
lengths against ``ContinuousBatchingScheduler`` on the wall clock and
reports request latency (p50/p99), time-to-first-token, and decode
throughput — plus the warm-restart row: a restarted engine + scheduler
over an already-populated plan cache must stage ZERO new plans.

The measurement runs in a CPU-only subprocess (``JAX_PLATFORMS=cpu``):
its numbers are XLA:CPU counts and times, not chip numbers.  Only one
process may hold a TPU, and the parent has already imported JAX, so this
suite does not run on a chip; ``chip_smoke.py`` is the on-chip path.  A
throwaway ``REPRO_CACHE_DIR`` makes the warm-restart measurement start
from a genuinely cold plan cache.

Standalone CI entry point::

    PYTHONPATH=src python -m benchmarks.bench_serving --smoke
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from . import common
from .common import csv_row

_CHILD = """
import time
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config, llama3_8b
from repro.core.cache import PlanCache
from repro.models import init_params
from repro.serve.engine import ServeEngine
from repro.sparse import random_pattern

quick = {quick}
cfg = get_config("llama3.2-3b", reduced=True)
params = init_params(cfg, jax.random.PRNGKey(0))
eng = ServeEngine(cfg, params, max_len=32)


def workload(kind, n, seed):
    \"\"\"(arrival_offset_s, prompt, max_new) triples: Poisson (exponential
    inter-arrival) or bursty (groups of 4 back-to-back, long gaps).\"\"\"
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for i in range(n):
        if kind == "poisson":
            t += float(rng.exponential(0.03))
        elif i % 4 == 0 and i > 0:
            t += 0.25  # burst gap
        P = int(rng.integers(4, 17))
        G = int(rng.integers(4, 17))
        prompt = rng.integers(0, cfg.vocab_size, size=(P,)).astype(np.int32)
        out.append((t, prompt, G))
    return out


def replay(kind, n, seed):
    \"\"\"Drive the scheduler against the wall clock: submit each request
    when its arrival time passes, step whenever lanes/queue have work.\"\"\"
    sched = eng.make_scheduler(page_size=8, max_batch=4)
    arrivals = workload(kind, n, seed)
    t0 = time.perf_counter()
    i = 0
    while i < len(arrivals) or sched.pending():
        now = time.perf_counter() - t0
        while i < len(arrivals) and arrivals[i][0] <= now:
            t, prompt, G = arrivals[i]
            sched.submit(prompt, G, rid=f"{{kind}}{{i}}", arrival=t0 + t)
            i += 1
        if sched.pending():
            sched.step()
        elif i < len(arrivals):
            time.sleep(min(arrivals[i][0] - now, 0.01))
    makespan = time.perf_counter() - t0
    lat, ttft = [], []
    for req in sched.requests.values():
        lat.append(req.metrics["finished_at"] - req.arrival)
        ttft.append(req.metrics["first_token_at"] - req.arrival)
    return {{
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
        "p50_ttft_s": float(np.percentile(ttft, 50)),
        "tokens_per_s": sched.stats["decode_tokens"] / max(makespan, 1e-9),
        "makespan_s": makespan,
        "steps": sched.stats["steps"],
        "evictions": sched.stats["evictions"],
        "finished": sched.stats["finished"],
    }}


def shared_prefix_replay(n, seed, *, sharing):
    \"\"\"Poisson arrivals where every prompt starts with the same 24-token
    system prompt — the page-sharing showcase.  Run once with sharing +
    chunked prefill ON and once OFF to measure the TTFT and page-footprint
    win; decode output is token-identical either way.\"\"\"
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    sched = eng.make_scheduler(
        page_size=8, max_batch=4, max_len=40,
        prefix_sharing=sharing, chunked_prefill=sharing,
    )
    arrivals = []
    t = 0.0
    for i in range(n):
        t += float(rng.exponential(0.03))
        P = int(rng.integers(2, 7))
        G = int(rng.integers(4, 9))
        suffix = rng.integers(0, cfg.vocab_size, size=(P,)).astype(np.int32)
        arrivals.append((t, np.concatenate([system, suffix]), G))
    t0 = time.perf_counter()
    i = 0
    while i < len(arrivals) or sched.pending():
        now = time.perf_counter() - t0
        while i < len(arrivals) and arrivals[i][0] <= now:
            at, prompt, G = arrivals[i]
            sched.submit(prompt, G, rid=f"sp{{i}}", arrival=t0 + at)
            i += 1
        if sched.pending():
            sched.step()
        elif i < len(arrivals):
            time.sleep(min(arrivals[i][0] - now, 0.01))
    makespan = time.perf_counter() - t0
    ttft = [
        req.metrics["first_token_at"] - req.arrival
        for req in sched.requests.values()
    ]
    return {{
        "p50_ttft_s": float(np.percentile(ttft, 50)),
        "p99_ttft_s": float(np.percentile(ttft, 99)),
        "makespan_s": makespan,
        "pages_allocated_total": sched.kv.allocator.total_allocated,
        "prefill_tokens": sched.stats["prefill_tokens"],
        "prefix_hits": sched.stats["prefix_hits"],
        "pages_shared": sched.stats["pages_shared"],
        "cow_copies": sched.stats["cow_copies"],
        "tokens": {{
            rid: np.asarray(req.tokens).tolist()
            for rid, req in sched.requests.items()
        }},
    }}


n = 12 if quick else 48
result = {{
    "poisson": replay("poisson", n, seed=1),
    "bursty": replay("bursty", n, seed=2),
    "shared_prefix_on": shared_prefix_replay(n, seed=3, sharing=True),
    "shared_prefix_off": shared_prefix_replay(n, seed=3, sharing=False),
}}

# ---- warm restart: engine warmup + scheduler admission stage zero plans
sable_cfg = llama3_8b.reduced_sable()
sable_params = init_params(sable_cfg, jax.random.PRNGKey(0))
t0 = time.perf_counter()
eng_cold = ServeEngine(sable_cfg, sable_params, max_len=16)
cold_s = time.perf_counter() - t0
t0 = time.perf_counter()
eng_warm = ServeEngine(sable_cfg, sable_params, max_len=16)
warm_s = time.perf_counter() - t0
store = PlanCache()
pat = (random_pattern(64, 64, 16, 16, 0.4, seed=5),)
rng = np.random.default_rng(9)
def serve_pat():
    sched = eng.make_scheduler(page_size=8, max_batch=2, plan_cache=store)
    for i in range(2):
        sched.submit(
            rng.integers(0, cfg.vocab_size, size=(6,)).astype(np.int32),
            4, patterns=pat, rid=f"w{{i}}{{time.perf_counter()}}",
        )
    sched.run()
    return sched.stats["plans_staged"]
result["warm_restart"] = {{
    "engine_cold_staged": eng_cold.warmup_stats["plans_staged"],
    "engine_warm_staged": eng_warm.warmup_stats["plans_staged"],
    "engine_warm_start": eng_warm.warmup_stats["warm_start"],
    "engine_cold_s": cold_s,
    "engine_warm_s": warm_s,
    "sched_cold_staged": serve_pat(),
    "sched_warm_staged": serve_pat(),
}}
print("RESULT " + json.dumps(result))
"""


def main(quick: bool = False) -> None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_CACHE_DIR"] = tempfile.mkdtemp(prefix="bench-serving-")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ["src", env.get("PYTHONPATH", ""), "."] if p
    )
    out = subprocess.run(
        [sys.executable, "-c", "import json\n" + _CHILD.format(quick=quick)],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"serving bench subprocess failed:\n{out.stdout}\n{out.stderr}"
        )
    result = None
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            result = json.loads(line[len("RESULT "):])
    assert result is not None, out.stdout
    for kind in ("poisson", "bursty"):
        r = result[kind]
        csv_row(
            f"serving/{kind}/latency",
            r["p50_latency_s"] * 1e6,
            f"p99_us={r['p99_latency_s'] * 1e6:.0f},"
            f"ttft_p50_us={r['p50_ttft_s'] * 1e6:.0f},"
            f"tok_per_s={r['tokens_per_s']:.1f},"
            f"evictions={r['evictions']},finished={r['finished']}",
        )
    on, off = result["shared_prefix_on"], result["shared_prefix_off"]
    # the sharing win must be real: hits registered, strictly fewer pages
    # ever allocated, fewer prefill tokens computed — and decode output
    # identical to the non-sharing run
    assert on["prefix_hits"] > 0, on
    assert on["pages_allocated_total"] < off["pages_allocated_total"], (on, off)
    assert on["prefill_tokens"] < off["prefill_tokens"], (on, off)
    assert on["tokens"] == off["tokens"], "sharing changed decode output"
    for label, r in (("on", on), ("off", off)):
        csv_row(
            f"serving/shared_prefix/{label}",
            r["p50_ttft_s"] * 1e6,
            f"ttft_p99_us={r['p99_ttft_s'] * 1e6:.0f},"
            f"pages_total={r['pages_allocated_total']},"
            f"prefill_tokens={r['prefill_tokens']},"
            f"prefix_hits={r['prefix_hits']},"
            f"pages_shared={r['pages_shared']},"
            f"cow_copies={r['cow_copies']}",
        )
    w = result["warm_restart"]
    assert w["engine_warm_staged"] == 0 and w["engine_warm_start"], w
    assert w["sched_warm_staged"] == 0, w
    csv_row(
        "serving/warm_restart/engine",
        w["engine_warm_s"] * 1e6,
        f"cold_us={w['engine_cold_s'] * 1e6:.0f},"
        f"cold_staged={w['engine_cold_staged']},warm_staged=0",
    )
    csv_row(
        "serving/warm_restart/scheduler",
        0.0,
        f"cold_staged={w['sched_cold_staged']},warm_staged=0",
    )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small workload, write BENCH_results.json")
    ap.add_argument("--json", default="BENCH_results.json")
    ap.add_argument("--no-json", action="store_true")
    args = ap.parse_args()
    common.CURRENT_SUITE = "serving"
    print("name,us_per_call,derived")
    main(quick=args.smoke)
    common.CURRENT_SUITE = None
    if not args.no_json:
        doc = {
            "version": 1,
            "mode": "smoke" if args.smoke else "quick",
            "failed_suites": [],
            "rows": common.ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {len(common.ROWS)} rows to {args.json}", file=sys.stderr)
