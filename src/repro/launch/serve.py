"""Serving launcher: single-batch generate or continuous batching.

Single-batch (the legacy path — one prefill, lockstep decode)::

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --batch 4 --prompt-len 32 --gen 32

Continuous batching (request-level scheduler over the paged KV cache,
mixed prompt/generation lengths, admission + eviction mid-decode)::

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
      --continuous --requests 12 --max-batch 4 --gen 32
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="request-level continuous batching (paged KV cache)")
    ap.add_argument("--requests", type=int, default=8,
                    help="[--continuous] number of mixed-length requests")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="[--continuous] decode lanes")
    ap.add_argument("--page-size", type=int, default=16,
                    help="[--continuous] KV page size (token positions)")
    ap.add_argument("--policy", default="fcfs",
                    choices=("fcfs", "warm_first"),
                    help="[--continuous] admission policy")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="[--continuous] share page-aligned prompt-prefix "
                         "pages across requests (copy-on-write)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="[--continuous] prefill long prompts in chunks "
                         "interleaved with decode steps")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="[--chunked-prefill] tokens per prefill chunk "
                         "(default 2*page_size)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="[--continuous] prepend this many identical tokens "
                         "to every prompt (demo workload for --prefix-sharing)")
    args = ap.parse_args()

    if args.devices:
        # forced host devices are a CPU feature; on a chip they would hide it
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            ap.error("--devices forces host CPU devices: set JAX_PLATFORMS=cpu")
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import numpy as np
    import jax
    import jax.numpy as jnp  # noqa: F401  (kept for interactive use)

    from ..configs import get_config
    from ..models.transformer import init_params
    from ..serve.engine import ServeEngine
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(
        cfg, params,
        max_len=args.shared_prefix + args.prompt_len + args.gen,
    )
    if engine.warmup_stats["plans_staged"]:
        print(f"staged {engine.warmup_stats['plans_staged']} sparse plans "
              "(cold cache); restart to serve warm")

    if args.continuous:
        rng = np.random.default_rng(1)
        shared = rng.integers(
            0, cfg.vocab_size, size=(args.shared_prefix,)
        ).astype(np.int32)
        reqs = []
        for i in range(args.requests):
            P = int(rng.integers(max(args.prompt_len // 4, 1),
                                 args.prompt_len + 1))
            G = int(rng.integers(max(args.gen // 4, 1), args.gen + 1))
            suffix = rng.integers(0, cfg.vocab_size, size=(P,)).astype(
                np.int32)
            reqs.append({
                "prompt": np.concatenate([shared, suffix]),
                "max_new_tokens": G,
                "temperature": args.temperature,
                "rng": jax.random.PRNGKey(i),
                "rid": f"req{i}",
            })
        t0 = time.perf_counter()
        results, sched = engine.serve(
            reqs, page_size=args.page_size, max_batch=args.max_batch,
            policy=args.policy,
            prefix_sharing=args.prefix_sharing,
            chunked_prefill=args.chunked_prefill,
            prefill_chunk=args.prefill_chunk,
        )
        dt = time.perf_counter() - t0
        s = sched.stats
        print(f"served {s['finished']} requests in {dt:.2f}s: "
              f"{s['steps']} steps, {s['decode_tokens']} decode tokens "
              f"({s['decode_tokens'] / max(dt, 1e-9):.1f} tok/s), "
              f"{s['evictions']} evictions, {s['resumes']} resumes")
        if args.prefix_sharing or args.chunked_prefill:
            print(f"prefix sharing: {s['prefix_hits']} hits, "
                  f"{s['pages_shared']} pages shared, "
                  f"{s['cow_copies']} COW copies; "
                  f"prefill {s['prefill_tokens']} tokens "
                  f"in {s['prefill_chunks']} chunks")
        first = results["req0"]
        print("first request:", first["tokens"][: first["prompt_len"] + 8].tolist())
        return

    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(
        rng, (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    t0 = time.perf_counter()
    out, stats = engine.generate(
        prompts, max_new_tokens=args.gen, temperature=args.temperature
    )
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"(prefill {stats['prefill_s']:.2f}s, "
          f"{stats['tokens_per_s']:.1f} tok/s decode)")
    print("first sequence:", out[0, : args.prompt_len + 8].tolist())


if __name__ == "__main__":
    main()
