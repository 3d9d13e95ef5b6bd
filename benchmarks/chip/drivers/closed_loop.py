"""Closed-loop batch generation through the program's serving path.

Traffic parameters (``workloads/<traffic>.json``):

* ``clients``: how many requests are in flight; each client sends its next
  request as soon as its last one finishes;
* ``max_batch``, ``page_size``, ``max_len``: the scheduler's settings;
* ``scheduler`` (optional): further keyword arguments for
  ``ServeEngine.make_scheduler``, such as ``prefix_sharing`` or
  ``chunked_prefill``;
* ``prompt_lens``, ``output_lens``: one round of requests.  Every seed
  gets the same rounds; the seed pairs the lengths, orders the requests
  and draws the prompt tokens, so every seed does the same work;
* ``rounds``: how many rounds are prepared (a window that uses them all
  starts over);
* ``check_requests``: requests of the window compared with the reference
  (every one served in the window, where there are no more);
* ``trace_seconds``: the longest window a traced run records.

Everything that belongs to the model's architecture comes from the
configuration's reference, through the contract that ``harness.py``
documents: the program's config (``program_config``), the weights
(``make_params``), the reference's logits (``scores``) and the operations a
token needs (``work``).

Set-up makes the weights on the device from ``--seed`` (the configuration's
reference draws them for the program's parameter tree), builds
``ServeEngine`` and its scheduler, serves one short request per prompt
length (which compiles every prefill shape and the decode step), and then
starts the clients with staggered first requests of 1..clients tokens, so
that the window starts in steady state and not with every lane admitting
at once.  The window drives ``submit``/``step``; a token's time is when the
``step()`` that produced it returned, on the client's side.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import harness
from tracecalc import span

CHECK_ROWS = 8  # requests the reference runs at once


def make_requests(traffic: dict, vocab: int, seed: int) -> list:
    """(prompt tokens, max_new_tokens) of every request, in sending order."""
    rng = np.random.default_rng(harness.seed_words(seed, 4))
    plens = np.asarray(traffic["prompt_lens"])
    olens = np.asarray(traffic["output_lens"])
    out = []
    for _ in range(int(traffic["rounds"])):
        order = rng.permutation(len(plens))
        pairing = rng.permutation(len(olens))
        for i, j in zip(order, pairing):
            prompt = rng.integers(0, vocab, size=int(plens[i]), dtype=np.int32)
            out.append((prompt, int(olens[j])))
    return out


@dataclasses.dataclass
class Tracked:
    rid: str
    prompt_len: int
    submitted: float
    lead: bool  # a staggered first request of the lead-in
    seen: int = 0
    stamps: list = dataclasses.field(default_factory=list)  # one per token


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    from repro.models.transformer import init_params
    from repro.serve.engine import ServeEngine

    cell, traffic = ctx.cell, ctx.cell.traffic
    cfg = cell.config
    ref = cell.reference()
    mcfg = ref.program_config(cfg)

    t0 = time.perf_counter()
    shapes = jax.eval_shape(lambda: init_params(mcfg, jax.random.PRNGKey(0)))
    params = jax.block_until_ready(ref.make_params(cfg, shapes, harness.jax_key(ctx.seed, 1)))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    harness.log(f"weights: {n_params} parameters ({mcfg.param_dtype}) made in "
                f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    engine = ServeEngine(mcfg, params, max_len=int(traffic["max_len"]))
    sched = engine.make_scheduler(page_size=int(traffic["page_size"]),
                                  max_batch=int(traffic["max_batch"]),
                                  **traffic.get("scheduler", {}))
    if ctx.fault is not None:
        ctx.fault(sched)
    harness.log(f"engine {time.perf_counter() - t0:.3f} s, warmup_stats {engine.warmup_stats}, "
                f"plans {engine.sparse_plans}")

    vocab = int(cfg["vocab_size"])
    t0 = time.perf_counter()
    rng = np.random.default_rng(harness.seed_words(ctx.seed, 3))
    for i, plen in enumerate(sorted(set(traffic["prompt_lens"]))):
        sched.submit(rng.integers(0, vocab, size=int(plen), dtype=np.int32), 2,
                     rid=f"warm{i}")
    sched.run()
    harness.log(f"warm-up (every prefill shape and the decode step) "
                f"{time.perf_counter() - t0:.3f} s")

    pending = make_requests(traffic, vocab, ctx.seed)
    clients = int(traffic["clients"])
    tracked: dict[str, Tracked] = {}
    finished: list[Tracked] = []
    next_req = [0]

    def send(max_new=None):
        prompt, n_new = pending[next_req[0] % len(pending)]
        rid = f"r{next_req[0]}"
        next_req[0] += 1
        with span("client.submit"):
            sched.submit(prompt, n_new if max_new is None else max_new, rid=rid)
        tracked[rid] = Tracked(rid, len(prompt), time.perf_counter(), max_new is not None)

    # staggered lead-in: client i's first request makes i+1 tokens
    for i in range(clients):
        send(max_new=i + 1)
    counts = {"decode_tokens": 0, "tokens_through_layers": 0, "keys_attended": 0,
              "logits_rows": 0, "steps": 0}

    def step(window: bool):
        with span("scheduler.step"):
            sched.step()
        now = time.perf_counter()
        for rid, t in list(tracked.items()):
            req = sched.requests[rid]
            new = len(req.tokens) - t.seen
            if new > 0 and window:
                if t.seen == 0:  # admitted this step: its prompt went through prefill
                    counts["tokens_through_layers"] += t.prompt_len
                    counts["keys_attended"] += t.prompt_len * (t.prompt_len + 1) // 2
                    counts["logits_rows"] += 1
                decoded = new - (1 if t.seen == 0 else 0)
                for j in range(decoded):
                    pos = t.prompt_len + t.seen + (0 if t.seen else 1) + j - 1
                    counts["keys_attended"] += pos + 1
                counts["tokens_through_layers"] += decoded
                counts["logits_rows"] += decoded
                counts["decode_tokens"] += decoded
            t.stamps.extend([now] * new)
            t.seen = len(req.tokens)
            if req.state == "FINISHED":
                finished.append(tracked.pop(rid))
                send()
        return now

    while any(t.lead for t in tracked.values()):
        step(False)
    setup_s = time.perf_counter() - ctx.t_process
    stats0 = dict(sched.stats)
    with ctx.tracer.window() as tracer:
        t_start = time.perf_counter()
        while True:
            now = step(True)
            counts["steps"] += 1
            if now - t_start >= ctx.seconds:
                break
    t_end = now
    peak = harness.memory_peak_bytes()
    stats1 = dict(sched.stats)
    window_s = t_end - t_start

    everyone = finished + list(tracked.values())
    ttft, itl, produced = [], [], 0
    for t in everyone:
        inside = [s for s in t.stamps if t_start < s <= t_end]
        produced += len(inside)
        if t.stamps and t_start < t.stamps[0] <= t_end and t.submitted >= t_start:
            ttft.append(t.stamps[0] - t.submitted)
        itl += [b - a for a, b in zip(t.stamps, t.stamps[1:]) if a > t_start and b <= t_end]
    harness.log(f"window {window_s:.6f} s, {counts['steps']} steps, {produced} tokens, "
                f"{len(ttft)} first tokens (TTFT samples), {len(itl)} token gaps (ITL samples), "
                f"{sum(1 for t in finished if t.stamps and t.stamps[-1] > t_start)} requests "
                f"finished; setup_s {setup_s:.6f}")
    harness.log(f"scheduler stats over the window: "
                f"{ {k: stats1[k] - stats0.get(k, 0) for k in stats1} }")
    e2e = {
        "tokens_per_s": produced / window_s,
        "itl_p95_ms": 1e3 * float(np.percentile(itl, 95)) if itl else float("nan"),
        "setup_s": setup_s,
    }
    harness.log(f"end to end: {e2e}; ITL median {1e3 * np.median(itl) if itl else 0:.3f} ms; "
                f"TTFT samples (ms) {[round(1e3 * x, 3) for x in ttft]}")

    # the check: the requests served in the window, finished or still in
    # flight at its close, against the reference, once the program's
    # scheduler and engine are gone
    served = [t for t in everyone if t.stamps and t.stamps[-1] > t_start]
    n_check = int(traffic["check_requests"])
    chosen = sample_for_check(served, n_check, ctx.seed)
    batch = reference_batch(sched, chosen, n_check, int(traffic["max_len"]))
    del sched, engine
    gap, control_gap = _check(ref, params, cfg, chosen, batch, control=ctx.control)
    attempted = sum(1 for t in everyone
                    if t.submitted <= t_end and (not t.stamps or t.stamps[-1] > t_start))
    counters = dict(counts, window_s=window_s, max_batch=int(traffic["max_batch"]),
                    sched_steps=stats1["steps"] - stats0["steps"],
                    sched_decode_tokens=stats1["decode_tokens"] - stats0["decode_tokens"],
                    flops=ref.work(cfg))
    if ctx.control:  # the control's gap is the one compared; the program's is kept
        counters["program_gap"] = gap
        gap = control_gap
    return harness.Outcome(
        end_to_end=e2e,
        checks={"max_logit_gap": (gap, cell.limits["max_logit_gap"])},
        attempted=attempted,
        failed=0,
        counters=counters,
        memory_peak_bytes=peak,
        trace_path=tracer.path,
    )


def sample_for_check(served: list, n: int, seed: int) -> list:
    """The request with the most served tokens, and others drawn from the seed."""
    if not served:
        return []
    longest = max(served, key=lambda t: (len(t.stamps), t.rid))
    rest = [t for t in served if t is not longest]
    rng = np.random.default_rng(harness.seed_words(seed, 5))
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(picks)]


def reference_batch(sched, chosen: list, n: int, max_len: int):
    """Token rows (prompt + served tokens but the last), padded to ``max_len``,
    with the served tokens as targets at the positions that produced them."""
    tokens = np.zeros((n, max_len), np.int32)
    targets = np.zeros((n, max_len), np.int32)
    mask = np.zeros((n, max_len), bool)
    for row, t in enumerate(chosen):
        req = sched.requests[t.rid]
        served = np.asarray(req.tokens, np.int32)
        seq = np.concatenate([np.asarray(req.prompt, np.int32), served[:-1]])
        tokens[row, :len(seq)] = seq
        p = len(req.prompt)
        targets[row, p - 1:p - 1 + len(served)] = served
        mask[row, p - 1:p - 1 + len(served)] = True
    return tokens, targets, mask


def _check(ref, params, cfg, chosen, batch, control: bool = False):
    """Widest gap by which a served token's logit lies below the reference's
    best, over a sample of the window's requests.  With
    ``control``, also the widest gap of the tokens that the control (the
    reference in the configuration's lower precision) puts first at the
    same positions; returns (served gap, control gap or None).  A control
    run compares the control's gap with the limit, so it reads not
    correct."""
    if not chosen:
        harness.log("check: no request was served in the window")
        return float("inf"), None
    tokens, targets, mask = batch
    t0 = time.perf_counter()
    best, got = [], []
    for i in range(0, len(tokens), CHECK_ROWS):  # blocks of rows, so that it fits
        tok, tgt = tokens[i:i + CHECK_ROWS], targets[i:i + CHECK_ROWS]
        rows = [tgt]
        if control:
            rows.append(ref.scores(params, cfg, tok, tgt[None], control=True)[2])
        b, g, _ = ref.scores(params, cfg, tok, np.stack(rows))
        best.append(b)
        got.append(g)
    best, got = np.concatenate(best), np.concatenate(got, axis=1)
    gaps = [(best - g)[mask] for g in got]
    harness.log(f"check: {len(chosen)} requests, {int(mask.sum())} served tokens against the "
                f"float32 reference in {time.perf_counter() - t0:.3f} s; widest gap "
                f"{float(gaps[0].max()):.6f}, median {float(np.median(gaps[0])):.6f}, "
                f"tokens the reference would not pick first {int((gaps[0] > 0).sum())}")
    if control:
        harness.log(f"control: widest gap of the {cfg.get('control_dtype')} control's first tokens "
                    f"{float(gaps[1].max()):.6f}, median {float(np.median(gaps[1])):.6f}, "
                    f"differing first tokens {int((gaps[1] > 0).sum())}")
    return float(gaps[0].max()), (float(gaps[1].max()) if control else None)
