"""Continuous-batching request scheduler over the paged KV cache.

The paper's amortization thesis at request granularity: a server admits and
retires sequences *mid-decode* (continuous batching) instead of running
fixed generate() batches, and every admission consults the persistent plan
cache (core/cache.py) so a structure whose plan is already warm fast-paths
straight to decode while cold structures are staged off the decode path
(at most ``cold_stage_budget`` patterns per scheduler iteration).

Scheduler states::

    WAITING ──admit (pages + lane free)──▶ RUNNING ──len(tokens)==max──▶ FINISHED
       ▲                                     │
       └──────── resume (lossless) ◀── PREEMPTED (pages parked on host)

One ``step()`` is one deterministic scheduling iteration: (0) stage cold
plans, (1) admit/resume from the queue, (1b) advance every mid-prefill
lane by one chunk (``chunked_prefill``), (2) grow page tables for this
step's write position — evicting the youngest-arrival lane under page
pressure — (3) one batched decode step over all running lanes, (4) retire
finished sequences.  Determinism is total given a fixed submission order
and clock: tests drive it with a fake clock and golden transcripts freeze
the admit/evict/page-table sequence.

Two opt-in features reuse the prompt across requests / unblock decode
under long prompts (both default off — the golden transcript pins the
plain schedule):

- ``prefix_sharing``: admission maps the page-aligned prompt prefix onto
  already-resident pages via the cache's prefix index (refcount + COW, see
  paged_cache.py) and prefills only the unshared tail — N requests with a
  common system prompt pay its pages and FLOPs once.
- ``chunked_prefill``: prompts prefill ``prefill_chunk`` tokens per
  scheduler step, interleaved with decode, instead of monopolizing a step;
  a mid-prefill lane holds pages but neither decodes nor blocks others,
  and eviction mid-prefill is lossless (resume continues at the next
  chunk).  Both features require a fully-paged cache (attention-only
  decoder): SSM/conv state summarizes the whole prefix and can be neither
  inherited from shared pages nor rebuilt chunk-by-chunk.

Decode is a single jitted ``vmap`` over lanes — each lane carries its own
cache view, position, RNG key, and temperature, so a lane's computation is
exactly the single-sequence ``decode_step`` and output tokens match N
independent ``ServeEngine.generate`` runs token-for-token.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from ..models.config import ModelConfig
from ..models.transformer import decode_step, init_cache, prefill
from ..models.transformer import prefill_chunk as _prefill_chunk_fn
from .paged_cache import (
    PagedKVCache, PagesExhausted, copy_pages, gather_view, write_slots,
)

__all__ = ["Request", "ContinuousBatchingScheduler"]

WAITING, RUNNING, PREEMPTED, FINISHED = (
    "WAITING",
    "RUNNING",
    "PREEMPTED",
    "FINISHED",
)

_RID = itertools.count()


@dataclasses.dataclass(eq=False)  # identity semantics (numpy fields)
class Request:
    """One generation request.  ``patterns`` (optional BlockPatterns) are
    the request's sparse structures for plan-warm admission; empty means
    dense / always warm."""

    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int
    temperature: float = 0.0
    rng: Optional[jnp.ndarray] = None  # per-request PRNG key (sampling)
    patterns: tuple = ()
    rid: str = ""
    arrival: float = 0.0
    state: str = WAITING
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: list = dataclasses.field(default_factory=list)
    skips: int = 0  # times passed over by warm-first admission (aging)
    prefilled: int = 0  # prompt positions whose KV is resident (shared or computed)
    metrics: dict = dataclasses.field(default_factory=dict)

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))

    def output(self) -> np.ndarray:
        return np.concatenate(
            [np.asarray(self.prompt, np.int32), np.asarray(self.tokens, np.int32)]
        )


def _make_lane_step(cfg: ModelConfig, kv: PagedKVCache):
    """Jitted per-step decoder over the device arenas of ``kv``: applies the
    queued page copies, gathers every lane's dense cache view through the
    int32 page table inside the trace, runs the vmap of the single-sequence
    decode over lanes with per-lane (view, position, key, temperature), and
    writes each lane's new KV slice into the page resolved for it.  Takes
    ``(params, tokens (B, 1), arenas, table (B, VP), states, positions (B,),
    keys, temperatures, write pages (B,), copy src, copy dst)``; returns
    ``(next_token (B,), logits (B, V) f32, (arenas, states, counts))`` with
    the arenas donated and updated in place; ``counts`` is
    ``{"moe_pairs": (B,) int32}`` for a model with MoE layers (each lane's
    (token, expert) pairs computed, over the layers) and empty otherwise.
    The program is named ``serve_lane_step`` (``jit_serve_lane_step`` in a
    trace)."""
    paged, treedef = list(kv.paged), kv.treedef
    moe = cfg.moe is not None

    def one(params, tok, cache_b, pos, rng, temp):
        cache1 = jax.tree.map(lambda a: a[:, None], cache_b)  # re-add B=1
        logits, nc, *pairs = decode_step(params, cfg, tok[None], cache1, pos, counts=moe)
        row = logits[:, 0].astype(jnp.float32)  # (1, V) — engine layout
        greedy = jnp.argmax(row, axis=-1)
        sampled = jax.random.categorical(
            rng, row / jnp.maximum(temp, 1e-6)
        )
        nxt = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)
        sl = [
            jax.lax.dynamic_slice_in_dim(a, pos, 1, axis=2)[:, 0, 0] if m else a
            for a, m in zip(jax.tree_util.tree_leaves(nc), paged)
        ]
        return nxt[0], row[0], sl, {"moe_pairs": pairs[0]} if moe else {}

    lanes = jax.vmap(one, in_axes=(None, 0, 1, 0, 0, 0))

    def serve_lane_step(
        params, toks, arenas, table, states, pos, keys, temps, pages, src, dst
    ):
        arenas = copy_pages(arenas, src, dst)
        views, st = iter([gather_view(a, table) for a in arenas]), iter(states)
        cache = jax.tree_util.tree_unflatten(
            treedef, [next(views) if m else next(st) for m in paged]
        )
        nxt, logits, sl, counts = lanes(params, toks, cache, pos, keys, temps)
        arenas = write_slots(
            arenas, pages, pos, [x for x, m in zip(sl, paged) if m]
        )
        return nxt, logits, (arenas, [x for x, m in zip(sl, paged) if not m], counts)

    return jax.jit(serve_lane_step, donate_argnums=2)


class ContinuousBatchingScheduler:
    """See module docstring.  ``policy``: "fcfs" (strict arrival order) or
    "warm_first" (plan-warm requests admit ahead of cold ones, with aging:
    a request skipped ``max_skips`` times regains head-of-line priority, so
    cold requests cannot starve).

    ``cold_cost_scoring=True`` refines warm_first with the learned cost
    model (``core/cost_model.py``): when no warm request exists, cold
    requests are admitted cheapest-predicted-staging-cost first (and
    ``_stage_cold`` stages cheapest first) instead of treating all cold as
    equal — many cheap structures warm per unit of staging time before one
    expensive one.  Off by default: scoring changes admission order, and
    golden transcripts pin the unscored schedule."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_len: int,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        max_batch: int = 4,
        policy: str = "fcfs",
        cold_stage_budget: int = 1,
        max_skips: int = 4,
        cold_cost_scoring: bool = False,
        clock=None,
        mesh=None,
        plan_cache=None,
        record_logits: bool = False,
        prefix_sharing: bool = False,
        chunked_prefill: bool = False,
        prefill_chunk: Optional[int] = None,
    ):
        if policy not in ("fcfs", "warm_first"):
            raise ValueError(f"unknown admission policy {policy!r}")
        self.cfg = cfg
        self.params = params
        self.max_len = int(max_len)
        self.max_batch = int(max_batch)
        self.policy = policy
        self.cold_stage_budget = int(cold_stage_budget)
        self.max_skips = int(max_skips)
        self.cold_cost_scoring = bool(cold_cost_scoring)
        self._stage_cost_model = False  # False = not resolved yet
        self.clock = clock if clock is not None else time.perf_counter
        self.mesh = mesh
        self.plan_cache = plan_cache
        self.record_logits = record_logits
        self.prefix_sharing = bool(prefix_sharing)
        self.chunked_prefill = bool(chunked_prefill)
        self.prefill_chunk = (
            2 * int(page_size) if prefill_chunk is None else int(prefill_chunk)
        )
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")

        import math

        view_pages = math.ceil(self.max_len / page_size)
        if num_pages is None:
            num_pages = self.max_batch * view_pages
        self.kv = PagedKVCache(
            cfg, num_pages, page_size, self.max_len,
            prefix_sharing=self.prefix_sharing,
        )
        if (self.prefix_sharing or self.chunked_prefill) and not all(
            self.kv.paged
        ):
            raise ValueError(
                "prefix_sharing/chunked_prefill need a fully-paged cache "
                "(attention-only decoder): SSM/conv state summarizes the "
                "whole prefix and cannot be shared or rebuilt per chunk"
            )

        # with MoE layers the prefills also return the (token, expert)
        # pairs they computed (``moe_pairs_here``)
        moe = cfg.moe is not None

        def serve_prefill(params, toks, cache):
            return prefill(params, cfg, toks, cache, counts=moe)

        def serve_prefill_chunk(params, toks, cache, start):
            return _prefill_chunk_fn(params, cfg, toks, cache, start, counts=moe)

        self._prefill = jax.jit(serve_prefill)
        self._prefill_chunk = jax.jit(serve_prefill_chunk)
        # fixed dense width for chunk compute: one retrace per chunk length
        self._prefill_width = self.kv.view_pages * int(page_size)
        self._lane_step = _make_lane_step(cfg, self.kv)

        self.queue: List[Request] = []  # kept in arrival order
        self.lanes: List[Optional[Request]] = [None] * self.max_batch
        self.requests: dict = {}
        self.transcript: list = []
        self.stats = {
            "steps": 0,
            "admissions": 0,
            "evictions": 0,
            "resumes": 0,
            "finished": 0,
            "plans_staged": 0,
            "decode_tokens": 0,
            "prefill_tokens": 0,
            "prefill_chunks": 0,
            "prefix_hits": 0,
            "pages_shared": 0,
            "cow_copies": 0,
            "shared_releases": 0,
            # host<->device bytes of the KV path (see repro.obs)
            "kv_h2d_bytes": 0,
            "kv_d2h_bytes": 0,
            "kv_parked_bytes": 0,
            "decode_steps_with_view": 0,
            # (token, expert) pairs the MoE layers computed (see repro.obs)
            "moe_pairs_here": 0,
        }

    # ------------------------------------------------------------------ #
    # submission
    # ------------------------------------------------------------------ #
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        temperature: float = 0.0,
        rng=None,
        patterns=(),
        rid: Optional[str] = None,
        arrival: Optional[float] = None,
    ) -> str:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + gen ({max_new_tokens}) exceeds "
                f"max_len={self.max_len}"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        req = Request(
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature),
            rng=rng if rng is not None else jax.random.PRNGKey(0),
            patterns=tuple(patterns),
            rid=rid if rid is not None else f"req{next(_RID)}",
            arrival=self.clock() if arrival is None else float(arrival),
        )
        if req.rid in self.requests:
            raise ValueError(f"duplicate rid {req.rid!r}")
        self.requests[req.rid] = req
        # arrival-ordered insert (preempted re-entries use the same path)
        self._enqueue(req)
        return req.rid

    def _enqueue(self, req: Request) -> None:
        i = len(self.queue)
        while i > 0 and self.queue[i - 1].arrival > req.arrival:
            i -= 1
        self.queue.insert(i, req)

    # ------------------------------------------------------------------ #
    # plan-warm admission
    # ------------------------------------------------------------------ #
    def _plan_keys(self, pattern) -> List[str]:
        from ..core import cache as cachelib
        from ..sparse.linear import pattern_hash

        device = jax.default_backend()
        h = pattern_hash(pattern)
        keys = [cachelib.plan_key("linear", h, device)]
        if self.mesh is not None:
            from ..core.sharded import resolve_shard_axis

            try:
                axis = resolve_shard_axis(self.mesh, "shards")
            except ValueError:
                axis = None
            if axis is not None:
                n = int(self.mesh.shape[axis])
                keys += [
                    cachelib.plan_key(
                        "linear", h, device, shard_id=i, num_shards=n
                    )
                    for i in range(n)
                ]
        return keys

    def _store(self):
        from ..core import cache as cachelib

        return (
            self.plan_cache
            if self.plan_cache is not None
            else cachelib.default_cache()
        )

    def _is_warm(self, req: Request) -> bool:
        store = self._store()
        return all(
            store.has_plan(k)
            for p in req.patterns
            for k in self._plan_keys(p)
        )

    # ------------------------------------------------------------------ #
    # predicted staging cost (cold_cost_scoring)
    # ------------------------------------------------------------------ #
    def _cost_model(self):
        """Lazily resolve the ``linear`` cost model over this scheduler's
        plan cache; None (no/too-small corpus) degrades scoring to the
        unscored behavior."""
        if self._stage_cost_model is False:
            from ..core import cost_model as cmlib

            self._stage_cost_model = cmlib.load_or_fit(
                self._store(), jax.default_backend(), "linear"
            )
        return self._stage_cost_model

    def _predicted_stage_cost(self, req: Request) -> float:
        """Predicted seconds to stage this request's still-cold patterns
        (sum over candidates — measuring times them all).  0.0 when warm;
        inf for a pattern the model cannot score (most expensive
        assumption, so scoreable work goes first)."""
        model = self._cost_model()
        if model is None:
            return 0.0
        from ..core import cost_model as cmlib

        store = self._store()
        total = 0.0
        for p in req.patterns:
            if all(store.has_plan(k) for k in self._plan_keys(p)):
                continue
            feats = cmlib.pattern_features(p)
            if model.nn_distance(feats) > cmlib.DEFAULT_MAX_DISTANCE:
                return float("inf")
            total += model.staging_cost(feats)
        return total

    def _stage_cold(self, ev: dict) -> None:
        """Stage up to ``cold_stage_budget`` cold patterns from the queue —
        off the decode path (decode proceeds this same iteration)."""
        if self.cold_stage_budget <= 0:
            return
        from ..sparse.linear import pattern_hash, warm_matmul_plans

        store = self._store()
        budget = self.cold_stage_budget
        seen = set()
        # waiting requests first, then running lanes: admission may outrun
        # staging (fcfs admits cold requests too), but every submitted
        # pattern must end up staged so the next process restarts warm
        pool = list(self.queue) + [r for r in self.lanes if r is not None]
        if self.cold_cost_scoring:
            # cheapest predicted staging first: the bounded budget warms
            # the most structures per scheduler iteration
            pool = sorted(
                enumerate(pool),
                key=lambda ir: (self._predicted_stage_cost(ir[1]), ir[0]),
            )
            pool = [r for _, r in pool]
        for req in pool:
            for p in req.patterns:
                h = pattern_hash(p)
                if h in seen:
                    continue
                seen.add(h)
                keys = self._plan_keys(p)
                cold = [k for k in keys if not store.has_plan(k)]
                if not cold:
                    continue
                warm_matmul_plans([p], cache=self.plan_cache, mesh=self.mesh)
                staged = sum(1 for k in cold if store.has_plan(k))
                self.stats["plans_staged"] += staged
                ev["staged"].append(h)
                budget -= 1
                if budget <= 0:
                    return

    # ------------------------------------------------------------------ #
    # admission / eviction
    # ------------------------------------------------------------------ #
    def _pick_next(self) -> Optional[int]:
        if not self.queue:
            return None
        if self.policy == "fcfs":
            return 0
        # warm_first with aging: an over-skipped head wins unconditionally
        if self.queue[0].skips >= self.max_skips:
            return 0
        for i, r in enumerate(self.queue):
            if self._is_warm(r):
                for o in self.queue[:i]:
                    o.skips += 1
                return i
        # every queued request is cold: score by predicted staging cost
        # (cheapest first) when enabled, else strict arrival order
        if self.cold_cost_scoring and len(self.queue) > 1:
            costs = [self._predicted_stage_cost(r) for r in self.queue]
            i = min(range(len(costs)), key=lambda j: (costs[j], j))
            if i > 0:
                for o in self.queue[:i]:
                    o.skips += 1
            return i
        return 0

    def _admit(self, now: float, ev: dict) -> None:
        while True:
            free = [i for i, r in enumerate(self.lanes) if r is None]
            if not free or not self.queue:
                return
            qi = self._pick_next()
            if qi is None:
                return
            req = self.queue[qi]
            if req.state == PREEMPTED:
                moved = self.kv.parked_bytes
                if not self.kv.resume(req.rid):
                    return  # head-of-line blocking on pages: deterministic
                self._count_parked(moved, "kv_h2d_bytes")
                self.stats["resumes"] += 1
                ev["resumed"].append(req.rid)
            elif self.prefix_sharing or self.chunked_prefill:
                if not self._begin_prefill(req, now, ev):
                    return
                ev["admitted"].append(req.rid)
            else:
                if not self.kv.alloc_seq(req.rid, req.prompt_len, zero=False):
                    return
                self._prefill_request(req)
                ev["admitted"].append(req.rid)
            self.queue.pop(qi)
            req.state = RUNNING
            self.stats["admissions"] += 1
            req.metrics.setdefault("admitted_at", now)
            if len(req.tokens) >= req.max_new_tokens:
                self._finish(req, free[0], self.clock(), ev, lane_assigned=False)
            else:
                self.lanes[free[0]] = req

    def _prefill_request(self, req: Request) -> None:
        """Whole-prompt prefill at admission — the plain path (both features
        off); frozen by the golden transcript, so it stays byte-stable."""
        P = req.prompt_len
        with obs.span("serve.prefill", rid=req.rid, tokens=P) as sp:
            cache = init_cache(self.cfg, 1, P)
            logits, cache, *pairs = self._prefill(
                self.params, jnp.asarray(req.prompt[None]), cache
            )
            row = logits[:, -1].astype(jnp.float32)  # (1, V)
            first = int(jnp.argmax(row, axis=-1)[0])
            self._count_pairs(pairs, sp)
        self.stats["kv_d2h_bytes"] += self.kv.write_prefill(req.rid, cache, P)
        self.stats["prefill_tokens"] += P
        req.prefilled = P
        req.tokens.append(first)
        if self.record_logits:
            req.logits.append(np.asarray(row[0]))
        req.metrics.setdefault("first_token_at", self.clock())

    # ------------------------------------------------------------------ #
    # prefix-shared / chunked prefill
    # ------------------------------------------------------------------ #
    def _begin_prefill(self, req: Request, now: float, ev: dict) -> bool:
        """Admission for the sharing/chunked path: attach the page-aligned
        shared prompt prefix by reference (pages + FLOPs skipped), reserve
        pages for the whole tail — or only the first chunk when chunking —
        and prefill the tail in one shot unless ``chunked_prefill`` defers
        it to ``_advance_prefills``.  False = not enough pages, admission
        blocks head-of-line (deterministic, like the plain path)."""
        P = req.prompt_len
        ok = self.kv.alloc_seq(
            req.rid,
            P,
            tokens=req.prompt if self.prefix_sharing else None,
            reserve=self.prefill_chunk if self.chunked_prefill else None,
            zero=False,
        )
        if not ok:
            return False
        req.prefilled = self.kv.seq_len[req.rid]  # == shared span
        if req.prefilled:
            ev["shared"][req.rid] = req.prefilled
        if not self.chunked_prefill:
            self._prefill_one_chunk(req, ev, in_admit=True)
        return True

    def _prefill_one_chunk(
        self, req: Request, ev: dict, in_admit: bool = False
    ) -> None:
        """Advance one mid-prefill sequence by one chunk (or the whole
        remaining tail when chunking is off).  The final chunk emits the
        first generated token from its last-position logits, exactly like
        whole-prompt prefill.  Page pressure parks other lanes per policy;
        if nothing is left to evict, this sequence parks itself losslessly
        (the computed chunk is dropped, ``prefilled`` does not advance)."""
        P = req.prompt_len
        if self.prefix_sharing and self.chunked_prefill:
            # the prefix writer may have registered pages since our last
            # chunk (or since admission): attach instead of recomputing
            if self.kv.attach_shared(req.rid):
                req.prefilled = self.kv.seq_len[req.rid]
                ev["shared"][req.rid] = req.prefilled
        start = req.prefilled
        end = P if not self.chunked_prefill else min(P, start + self.prefill_chunk)
        while not self.kv.ensure_capacity(req.rid, end, zero=False):
            others = [
                r for r in self.lanes if r is not None and r is not req
            ]
            if others:
                victim = max(others, key=lambda r: (r.arrival, r.rid))
                self._evict(victim, ev)
                continue
            if self._release_parked_shared_one():
                continue
            if in_admit:  # capacity was reserved at alloc; unreachable
                raise PagesExhausted(f"admission reserve lost for {req.rid!r}")
            self._evict(req, ev)
            return
        # gathered on the device; only host leaves (none: the cache is
        # fully paged here) would cross
        dense = self.kv.read_dense(req.rid, s_max=self._prefill_width)
        h2d = sum(
            int(x.nbytes) for x in jax.tree.leaves(dense)
            if not isinstance(x, jax.Array)
        )
        with obs.span("kv.upload", bytes_h2d=h2d):
            dense = jax.block_until_ready(jax.device_put(dense))
        self.stats["kv_h2d_bytes"] += h2d
        with obs.span("serve.prefill", rid=req.rid, tokens=end - start) as sp:
            logits, dense, *pairs = self._prefill_chunk(
                self.params,
                jnp.asarray(req.prompt[None, start:end]),
                dense,
                jnp.int32(start),
            )
            if end == P:
                row = logits[:, -1].astype(jnp.float32)  # (1, V)
                first = int(jnp.argmax(row, axis=-1)[0])
            self._count_pairs(pairs, sp)
        self.stats["kv_d2h_bytes"] += self.kv.write_span(req.rid, dense, start, end)
        req.prefilled = end
        self.stats["prefill_tokens"] += end - start
        self.stats["prefill_chunks"] += 1
        ev["prefill"][req.rid] = [start, end]
        if end == P:
            req.tokens.append(first)
            if self.record_logits:
                req.logits.append(np.asarray(row[0]))
            req.metrics.setdefault("first_token_at", self.clock())

    def _advance_prefills(self, ev: dict) -> None:
        """One chunk per mid-prefill lane per step, oldest arrival first —
        interleaved with decode so long prompts never stall running lanes."""
        if not self.chunked_prefill:
            return
        order = sorted(
            (
                i
                for i, r in enumerate(self.lanes)
                if r is not None and r.prefilled < r.prompt_len
            ),
            key=lambda i: (self.lanes[i].arrival, self.lanes[i].rid),
        )
        for i in order:
            req = self.lanes[i]
            if req is None or req.prefilled >= req.prompt_len:
                continue  # evicted by an earlier lane's page pressure
            self._prefill_one_chunk(req, ev)
            # max_new_tokens == 1: the final chunk's token is the output
            if (
                self.lanes[i] is req
                and req.tokens
                and len(req.tokens) >= req.max_new_tokens
            ):
                self._finish(req, i, self.clock(), ev)

    def _count_pairs(self, pairs, sp) -> None:
        """Add a program's MoE pair count (``[]`` without MoE layers) to
        ``moe_pairs_here`` and to the span ``sp``."""
        if pairs:
            n = int(np.sum(pairs[0]))
            self.stats["moe_pairs_here"] += n
            sp.set_metadata(moe_pairs=n)

    def _release_parked_shared_one(self) -> bool:
        """Terminal-pressure escape valve: demote the youngest parked
        sequence's retained shared pages to host copies so the arena can
        actually drain.  False when no parked sequence holds shared pages
        (always, with sharing off — the plain eviction order is untouched)."""
        if not self.prefix_sharing:
            return False
        parked = [
            r
            for r in self.queue
            if r.state == PREEMPTED
            and self.kv.is_parked(r.rid)
            and self.kv.parked_shared_pages(r.rid) > 0
        ]
        if not parked:
            return False
        victim = max(parked, key=lambda r: (r.arrival, r.rid))
        moved = self.kv.parked_bytes
        self.kv.release_parked_shared(victim.rid)
        self._count_parked(moved, "kv_d2h_bytes")
        self.stats["shared_releases"] += 1
        return True

    def _count_parked(self, before: int, direction: str) -> None:
        """Count the KV bytes the cache parked or restored since its
        ``parked_bytes`` read ``before``: the only KV that crosses."""
        moved = self.kv.parked_bytes - before
        self.stats["kv_parked_bytes"] += moved
        self.stats[direction] += moved

    def _evict(self, req: Request, ev: dict) -> None:
        lane = self.lanes.index(req)
        moved = self.kv.parked_bytes
        self.kv.evict(req.rid)
        self._count_parked(moved, "kv_d2h_bytes")
        self.lanes[lane] = None
        req.state = PREEMPTED
        self.stats["evictions"] += 1
        ev["evicted"].append(req.rid)
        self._enqueue(req)

    def _ensure_growth(self, ev: dict) -> List[int]:
        """Reserve this step's write position for every decoding lane,
        evicting the youngest-arrival lane under page pressure.  Returns
        the lane indices that will decode this step (mid-prefill lanes hold
        pages but neither grow nor decode here)."""
        order = sorted(
            (i for i, r in enumerate(self.lanes) if r is not None),
            key=lambda i: (self.lanes[i].arrival, self.lanes[i].rid),
        )
        for i in list(order):
            req = self.lanes[i]
            if req is None or not req.tokens:
                continue
            # this step consumes tokens[-1], writing its KV at position
            # prompt_len + len(tokens) - 1 — reserve exactly that
            while not self.kv.ensure_capacity(
                req.rid, req.prompt_len + len(req.tokens)
            ):
                running = [r for r in self.lanes if r is not None]
                victim = max(running, key=lambda r: (r.arrival, r.rid))
                if victim is req and self._release_parked_shared_one():
                    continue
                self._evict(victim, ev)
                if victim is req:
                    break
        return [i for i, r in enumerate(self.lanes) if r is not None and r.tokens]

    def _finish(self, req, lane, now, ev, lane_assigned=True) -> None:
        self.kv.free_seq(req.rid)
        if lane_assigned:
            self.lanes[lane] = None
        req.state = FINISHED
        req.metrics["finished_at"] = now
        self.stats["finished"] += 1
        ev["finished"].append(req.rid)

    # ------------------------------------------------------------------ #
    # the scheduling iteration
    # ------------------------------------------------------------------ #
    def step(self) -> dict:
        with obs.span("serve.step") as sp:
            ev = self._step()
            sp.set_metadata(running=len(ev["running"]), admitted=len(ev["admitted"]))
        return ev

    def _step(self) -> dict:
        now = self.clock()
        ev = {
            "step": self.stats["steps"],
            "admitted": [],
            "resumed": [],
            "evicted": [],
            "finished": [],
            "staged": [],
            "running": [],
            "page_tables": {},
        }
        if self.prefix_sharing or self.chunked_prefill:
            # gated: the frozen transcript compares events by full-dict
            # equality, so the plain schedule must not grow keys
            ev["shared"] = {}
            ev["prefill"] = {}
        self._stage_cold(ev)
        with obs.span("serve.admit"):
            self._admit(now, ev)
        self._advance_prefills(ev)
        active = self._ensure_growth(ev)
        ev["running"] = [self.lanes[i].rid for i in active]
        ev["page_tables"] = {
            self.lanes[i].rid: list(self.kv.page_table[self.lanes[i].rid])
            for i in active
        }
        if active:
            with obs.span("serve.decode", lanes=len(active)) as sp:
                self._count_pairs(self._decode_once(active, ev), sp)
        self.stats["steps"] += 1
        for k, v in self.kv.share_stats.items():
            self.stats[k] = v
        self.transcript.append(ev)
        return ev

    def _reserve_writes(self, active: List[int], ev: dict) -> dict:
        """Resolve the page every decoding lane writes this step (growth,
        COW) before the device runs.  ``PagesExhausted`` evicts per policy
        (youngest other lane first), then tries the shared-page escape
        valve, then parks the lane itself losslessly: a parked lane takes
        no part in the step, so nothing computed is lost and its rng is
        never split.  Returns ``{lane: request}`` of the lanes that
        decode."""
        for i in active:
            req = self.lanes[i]
            if req is None:
                continue  # parked by an earlier lane's page pressure
            pos = req.prompt_len + len(req.tokens) - 1
            while True:
                try:
                    self.kv.append_token(req.rid, None, pos)
                    break
                except PagesExhausted:
                    others = [
                        r for r in self.lanes if r is not None and r is not req
                    ]
                    if others:
                        self._evict(max(others, key=lambda r: (r.arrival, r.rid)), ev)
                        continue
                    if self._release_parked_shared_one():
                        continue
                    self._evict(req, ev)
                    break
        return {i: self.lanes[i] for i in active if self.lanes[i] is not None}

    def _decode_once(self, active: List[int], ev: dict) -> list:
        """One lane step over the ``active`` lanes; returns the MoE pair
        counts of the lanes that decoded (``[]`` without MoE layers)."""
        B = self.max_batch
        with obs.span("kv.gather") as sp:
            lane_reqs = self._reserve_writes(active, ev)
            if not lane_reqs:
                return []  # the only lane parked itself
            rids = [lane_reqs[i].rid if i in lane_reqs else None for i in range(B)]
            table = self.kv.gather(rids)
            pages = self.kv.step_pages(rids)
            # a lane queues at most a zeroed page and a COW copy a step
            src, dst = self.kv.take_copies(2 * B)
            states = self.kv.lane_states(rids)
            sp.set_metadata(bytes=int(table.nbytes))
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        keys = []
        zero_key = np.zeros_like(np.asarray(jax.random.PRNGKey(0)))
        for i in range(B):
            req = lane_reqs.get(i)
            if req is None:
                keys.append(zero_key)
                continue
            toks[i, 0] = req.tokens[-1]
            pos[i] = req.prompt_len + len(req.tokens) - 1
            temps[i] = req.temperature
            # mirror ServeEngine.generate: split every step, sample with sub
            req.rng, sub = jax.random.split(req.rng)
            keys.append(np.asarray(sub))
        host = [toks, table, pos, np.stack(keys), temps, pages, src, dst] + states
        # the step cannot start before its arguments are on the device, so
        # waiting for the upload here adds no wait; it only names the transfer
        h2d = sum(int(x.nbytes) for x in host)
        with obs.span("kv.upload", bytes_h2d=h2d):
            dev = jax.block_until_ready(jax.device_put(host))
        self.stats["kv_h2d_bytes"] += h2d
        self.stats["decode_steps_with_view"] += 1
        d_toks, d_table, d_pos, d_keys, d_temps, d_pages, d_src, d_dst = dev[:8]
        with obs.span("serve.lane_step"):
            nxt, logits, (arenas, new_states, counts) = jax.block_until_ready(
                self._lane_step(
                    self.params, d_toks, self.kv.arenas, d_table, dev[8:],
                    d_pos, d_keys, d_temps, d_pages, d_src, d_dst,
                )
            )
        self.kv.arenas = arenas
        kv_bytes = sum(int(x.nbytes) for x in new_states)
        d2h = int(nxt.nbytes) + int(logits.nbytes) + kv_bytes + sum(
            int(x.nbytes) for x in counts.values())
        with obs.span("serve.readback", bytes_d2h=d2h, kv_bytes=kv_bytes):
            nxt = np.asarray(nxt)
            logits = np.asarray(logits)
            new_states = [np.asarray(x) for x in new_states]
            counts = {k: np.asarray(x) for k, x in counts.items()}
        self.stats["kv_d2h_bytes"] += kv_bytes
        now = self.clock()
        with obs.span("kv.append", lanes=len(lane_reqs)):
            for i, req in lane_reqs.items():
                self.kv.commit_token(req.rid, [x[i] for x in new_states])
                req.tokens.append(int(nxt[i]))
                if self.record_logits:
                    req.logits.append(logits[i])
                self.stats["decode_tokens"] += 1
                if len(req.tokens) >= req.max_new_tokens:
                    self._finish(req, i, now, ev)
        if "moe_pairs" not in counts:
            return []
        return [counts["moe_pairs"][sorted(lane_reqs)]]

    # ------------------------------------------------------------------ #
    def pending(self) -> int:
        return len(self.queue) + sum(r is not None for r in self.lanes)

    def run(self, max_steps: int = 100_000) -> dict:
        """Drive ``step()`` until every submitted request finished."""
        while self.pending() and self.stats["steps"] < max_steps:
            self.step()
        if self.pending():
            raise RuntimeError(
                f"scheduler did not drain in {max_steps} steps "
                f"(queue={len(self.queue)})"
            )
        return {
            rid: {
                "tokens": req.output(),
                "prompt_len": req.prompt_len,
                "metrics": dict(req.metrics),
                "state": req.state,
            }
            for rid, req in self.requests.items()
        }
