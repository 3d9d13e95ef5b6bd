"""Continuous-batching scheduler: equivalence, invariants, plan-warm admission.

The core contract: N concurrent requests of mixed lengths decoded by the
continuous-batching scheduler produce tokens IDENTICAL (and logits within
1e-6) to N independent single-sequence ``ServeEngine.generate`` runs — for
greedy and sampled decoding, through forced eviction/resume, and with a
1-D device mesh attached.  Scheduling itself is exercised with seeded fake
clocks: deterministic transcripts, capacity invariants every step, no
starvation under either admission policy.
"""
import dataclasses
import itertools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.cache import PlanCache
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serve.engine import ServeEngine
from repro.sparse import random_pattern

from test_distributed import run_with_devices


@pytest.fixture(scope="module")
def engine():
    """f32 reduced llama engine — the single-sequence numeric reference."""
    cfg = get_config("llama3.2-3b", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, max_len=20)


def _prompts(cfg, lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
        for n in lengths
    ]


def _reference(engine, prompt, max_new, temperature=0.0, rng=None):
    out, _ = engine.generate(
        jnp.asarray(prompt)[None], max_new, temperature=temperature, rng=rng
    )
    return np.asarray(out)[0]


def _fake_clock(step=0.5):
    counter = itertools.count()
    return lambda: next(counter) * step


# ---------------------------------------------------------------------- #
# token + logit equivalence vs N independent generate() runs
# ---------------------------------------------------------------------- #
def test_greedy_matches_independent_generate_with_logits(engine):
    cfg = engine.cfg
    prompts = _prompts(cfg, [3, 7, 5])
    gens = [6, 4, 8]
    reqs = [
        {"prompt": p, "max_new_tokens": g, "rid": f"r{i}"}
        for i, (p, g) in enumerate(zip(prompts, gens))
    ]
    results, sched = engine.serve(
        reqs, page_size=4, max_batch=3, record_logits=True
    )
    for i, (p, g) in enumerate(zip(prompts, gens)):
        ref = _reference(engine, p, g)
        np.testing.assert_array_equal(results[f"r{i}"]["tokens"], ref)
        # logits within 1e-6 of the single-sequence path, step by step
        P = len(p)
        cache = init_cache(cfg, 1, P + g)
        logits, cache = prefill(engine.params, cfg, jnp.asarray(p)[None], cache)
        rows = [np.asarray(logits[:, -1].astype(jnp.float32))[0]]
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)[:, None].astype(
            jnp.int32
        )
        for j in range(g - 1):
            lg, cache = decode_step(
                engine.params, cfg, nxt, cache, jnp.int32(P + j)
            )
            row = lg[:, 0].astype(jnp.float32)
            rows.append(np.asarray(row)[0])
            nxt = jnp.argmax(row, -1)[:, None].astype(jnp.int32)
        got = sched.requests[f"r{i}"].logits
        assert len(got) == len(rows) == g
        for a, b in zip(got, rows):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def test_sampling_matches_independent_generate(engine):
    cfg = engine.cfg
    prompts = _prompts(cfg, [4, 6, 3], seed=11)
    reqs = [
        {
            "prompt": p,
            "max_new_tokens": 6,
            "temperature": 0.8,
            "rng": jax.random.PRNGKey(100 + i),
            "rid": f"s{i}",
        }
        for i, p in enumerate(prompts)
    ]
    results, _ = engine.serve(reqs, page_size=4, max_batch=3)
    for i, p in enumerate(prompts):
        ref = _reference(
            engine, p, 6, temperature=0.8, rng=jax.random.PRNGKey(100 + i)
        )
        np.testing.assert_array_equal(results[f"s{i}"]["tokens"], ref)


def test_eviction_and_resume_are_lossless(engine):
    """Page pressure forces mid-decode eviction; the preempted sequence
    resumes bit-for-bit, so final tokens still match independent runs."""
    cfg = engine.cfg
    prompts = _prompts(cfg, [6, 6, 6], seed=23)
    reqs = [
        {"prompt": p, "max_new_tokens": 8, "rid": f"e{i}"}
        for i, p in enumerate(prompts)
    ]
    # 3 lanes x final length 13 = 4 pages each (12 total) but only 9 pages
    results, sched = engine.serve(
        reqs, page_size=4, max_batch=3, num_pages=9
    )
    assert sched.stats["evictions"] > 0, "test must exercise eviction"
    assert sched.stats["resumes"] > 0
    for i, p in enumerate(prompts):
        ref = _reference(engine, p, 8)
        np.testing.assert_array_equal(results[f"e{i}"]["tokens"], ref)
        assert results[f"e{i}"]["state"] == "FINISHED"


def test_more_requests_than_lanes_all_finish_fcfs(engine):
    cfg = engine.cfg
    prompts = _prompts(cfg, [3, 5, 4, 6, 2, 4], seed=31)
    reqs = [
        {"prompt": p, "max_new_tokens": 3 + (i % 3), "rid": f"q{i}"}
        for i, p in enumerate(prompts)
    ]
    results, sched = engine.serve(reqs, page_size=4, max_batch=2)
    assert sched.stats["finished"] == len(reqs)
    for i, p in enumerate(prompts):
        ref = _reference(engine, p, 3 + (i % 3))
        np.testing.assert_array_equal(results[f"q{i}"]["tokens"], ref)


# ---------------------------------------------------------------------- #
# event-driven simulation: fake clock, invariants every step
# ---------------------------------------------------------------------- #
def test_step_invariants_under_fake_clock(engine):
    cfg = engine.cfg
    sched = engine.make_scheduler(
        page_size=4, max_batch=2, num_pages=8, clock=_fake_clock()
    )
    for i, p in enumerate(_prompts(cfg, [5, 3, 6, 4], seed=41)):
        sched.submit(p, max_new_tokens=5, rid=f"c{i}", arrival=float(i))
    kv = sched.kv
    seen_running = set()
    while sched.pending():
        ev = sched.step()
        # capacity never exceeded, allocator never leaks or double-books
        kv.allocator.check()
        assert kv.allocator.num_held <= kv.allocator.num_pages
        assert sum(r is not None for r in sched.lanes) <= sched.max_batch
        assert len(ev["running"]) <= sched.max_batch
        held = sum(len(t) for t in kv.page_table.values())
        assert held == kv.allocator.num_held
        seen_running.update(ev["running"])
        assert sched.stats["steps"] < 500
    assert seen_running == {f"c{i}" for i in range(4)}  # no starvation
    assert kv.allocator.num_free == kv.allocator.num_pages  # all released
    for i in range(4):
        m = sched.requests[f"c{i}"].metrics
        # timestamps come from the fake clock and are ordered
        assert 0 <= m["admitted_at"] <= m["finished_at"]
        assert m["first_token_at"] <= m["finished_at"]


def test_transcript_is_deterministic_in_lengths_only(engine):
    """Admission/eviction/page tables depend only on integer lengths and
    arrival order — never token values — so two runs over different
    prompts of the same lengths yield identical transcripts (the property
    the golden serving fixture freezes)."""
    cfg = engine.cfg
    lengths, gens = [6, 6, 5], [6, 5, 6]

    def transcript(seed):
        sched = engine.make_scheduler(
            page_size=4, max_batch=2, num_pages=7, clock=_fake_clock()
        )
        for i, p in enumerate(_prompts(cfg, lengths, seed=seed)):
            sched.submit(p, max_new_tokens=gens[i], rid=f"t{i}", arrival=float(i))
        sched.run()
        return sched.transcript

    assert transcript(seed=1) == transcript(seed=2)


# ---------------------------------------------------------------------- #
# plan-warm admission
# ---------------------------------------------------------------------- #
def test_cold_plans_staged_once_then_warm_restart_stages_zero(engine, tmp_path):
    """Cold patterns are staged off the decode path (bounded per step); a
    restarted scheduler over the same persistent cache stages ZERO."""
    cfg = engine.cfg
    store = PlanCache(str(tmp_path))
    pats = tuple(
        random_pattern(64, 64, 16, 16, 0.4, seed=s) for s in (0, 1)
    )
    prompts = _prompts(cfg, [4, 5], seed=51)

    def serve_once():
        sched = engine.make_scheduler(
            page_size=4, max_batch=2, plan_cache=store,
            cold_stage_budget=1, clock=_fake_clock(),
        )
        for i, p in enumerate(prompts):
            sched.submit(
                p, max_new_tokens=4, patterns=pats, rid=f"p{i}",
                arrival=float(i),
            )
        results = sched.run()
        return results, sched

    results, sched = serve_once()
    assert sched.stats["plans_staged"] >= len(pats)
    assert all(r["state"] == "FINISHED" for r in results.values())
    staged_events = [ev for ev in sched.transcript if ev["staged"]]
    assert all(len(ev["staged"]) <= 1 for ev in staged_events)  # budget
    # "restart": a fresh scheduler over the same on-disk plan cache
    results2, sched2 = serve_once()
    assert sched2.stats["plans_staged"] == 0, "warm restart must not re-stage"
    np.testing.assert_array_equal(
        results["p0"]["tokens"], results2["p0"]["tokens"]
    )


def test_warm_first_policy_reorders_but_never_starves(engine, tmp_path):
    """warm_first admits plan-warm requests ahead of cold ones; aging
    (max_skips) guarantees the cold head still runs."""
    cfg = engine.cfg
    store = PlanCache(str(tmp_path))
    cold_pat = (random_pattern(64, 64, 16, 16, 0.4, seed=9),)
    prompts = _prompts(cfg, [4, 4, 4], seed=61)
    sched = engine.make_scheduler(
        page_size=4, max_batch=1, plan_cache=store, policy="warm_first",
        cold_stage_budget=0,  # never stage: the cold request stays cold
        max_skips=2, clock=_fake_clock(),
    )
    sched.submit(prompts[0], 4, patterns=cold_pat, rid="cold", arrival=0.0)
    sched.submit(prompts[1], 4, rid="warm1", arrival=1.0)
    sched.submit(prompts[2], 4, rid="warm2", arrival=2.0)
    results = sched.run()
    assert all(r["state"] == "FINISHED" for r in results.values())
    assert sched.stats["plans_staged"] == 0
    m = {rid: sched.requests[rid].metrics["admitted_at"] for rid in results}
    # a later-arriving warm request was admitted before the cold head...
    assert m["warm1"] < m["cold"]
    # ...and tokens are still exactly the single-sequence reference
    np.testing.assert_array_equal(
        results["cold"]["tokens"], _reference(engine, prompts[0], 4)
    )


def test_warm_first_without_aging_would_not_default(engine):
    with pytest.raises(ValueError):
        engine.make_scheduler(policy="best_effort")


def _seed_linear_corpus(store, n=10):
    """Measured `linear` plans with planted timings proportional to tile
    count, so the cost model ranks patterns by n_tiles."""
    from repro.core import cost_model as cmlib
    from repro.core.cache import TuningPlan, plan_key
    from repro.core.staging import StagingOptions
    from repro.sparse.linear import pattern_hash

    for i in range(n):
        p = random_pattern(64, 64, 8, 8, 0.15 + 0.08 * i, seed=300 + i)
        feats = cmlib.pattern_features(p)
        store.store_plan(
            plan_key("linear", pattern_hash(p), "cpu"),
            TuningPlan(
                kind="linear", structure_hash=pattern_hash(p),
                options=StagingOptions(backend="grouped", tile=(8, 8)),
                device="cpu",
                timings={"grouped": float(np.exp(-10 + 0.9 * feats[2]))},
                meta={"d_in": p.d_in, "d_out": p.d_out, "tm": p.tm,
                      "tk": p.tk, "n_tiles": p.n_tiles,
                      "density": p.density},
                source="measured",
            ),
        )


def test_cold_cost_scoring_admits_cheapest_staging_first(engine, tmp_path):
    """With cold_cost_scoring, an all-cold queue admits the request whose
    patterns the model predicts cheapest to stage — not arrival order."""
    cfg = engine.cfg
    store = PlanCache(str(tmp_path))
    _seed_linear_corpus(store)
    expensive = (random_pattern(64, 64, 8, 8, 0.85, seed=401),)
    cheap = (random_pattern(64, 64, 8, 8, 0.2, seed=402),)
    prompts = _prompts(cfg, [4, 4], seed=71)
    sched = engine.make_scheduler(
        page_size=4, max_batch=1, plan_cache=store, policy="warm_first",
        cold_cost_scoring=True, cold_stage_budget=0, max_skips=10,
        clock=_fake_clock(),
    )
    sched.submit(prompts[0], 4, patterns=expensive, rid="slow", arrival=0.0)
    sched.submit(prompts[1], 4, patterns=cheap, rid="fast", arrival=1.0)
    results = sched.run()
    assert all(r["state"] == "FINISHED" for r in results.values())
    m = {rid: sched.requests[rid].metrics["admitted_at"] for rid in results}
    assert m["fast"] < m["slow"]  # later arrival, cheaper predicted staging
    np.testing.assert_array_equal(
        results["slow"]["tokens"], _reference(engine, prompts[0], 4)
    )


def test_cold_cost_scoring_off_keeps_arrival_order(engine, tmp_path):
    """Default (scoring off): the same all-cold queue admits in arrival
    order — the golden-transcript behavior."""
    cfg = engine.cfg
    store = PlanCache(str(tmp_path))
    _seed_linear_corpus(store)
    expensive = (random_pattern(64, 64, 8, 8, 0.85, seed=401),)
    cheap = (random_pattern(64, 64, 8, 8, 0.2, seed=402),)
    prompts = _prompts(cfg, [4, 4], seed=71)
    sched = engine.make_scheduler(
        page_size=4, max_batch=1, plan_cache=store, policy="warm_first",
        cold_stage_budget=0, max_skips=10, clock=_fake_clock(),
    )
    sched.submit(prompts[0], 4, patterns=expensive, rid="slow", arrival=0.0)
    sched.submit(prompts[1], 4, patterns=cheap, rid="fast", arrival=1.0)
    results = sched.run()
    m = {rid: sched.requests[rid].metrics["admitted_at"] for rid in results}
    assert m["slow"] < m["fast"]


# ---------------------------------------------------------------------- #
# 1-D mesh path: scheduler composes with sharded staging
# ---------------------------------------------------------------------- #
def test_mesh_scheduler_matches_generate_and_warms_shard_plans():
    run_with_devices("""
        import dataclasses, tempfile
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.core.cache import PlanCache
        from repro.launch.mesh import make_staging_mesh
        from repro.models import init_params
        from repro.serve.engine import ServeEngine
        from repro.sparse import random_pattern

        cfg = get_config("llama3.2-3b", reduced=True)
        cfg = dataclasses.replace(
            cfg, compute_dtype="float32", param_dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh = make_staging_mesh(2)
        eng = ServeEngine(cfg, params, max_len=20, mesh=mesh)

        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, size=(n,)).astype(np.int32)
                   for n in (3, 6, 4)]
        store = PlanCache(tempfile.mkdtemp())
        pat = (random_pattern(64, 64, 16, 16, 0.4, seed=2),)
        reqs = [{"prompt": p, "max_new_tokens": 5, "rid": f"m{i}",
                 "patterns": pat, "arrival": float(i)}
                for i, p in enumerate(prompts)]
        results, sched = eng.serve(
            reqs, page_size=4, max_batch=2, plan_cache=store)
        assert sched.mesh is mesh
        # base plan + one per shard of the 1-D mesh were staged at admission
        assert sched.stats["plans_staged"] >= 3, sched.stats
        for i, p in enumerate(prompts):
            out, _ = eng.generate(jnp.asarray(p)[None], 5)
            np.testing.assert_array_equal(
                results[f"m{i}"]["tokens"], np.asarray(out)[0])
        print("MESH-EQ-OK")
    """, n=2)


# ---------------------------------------------------------------------- #
# prefix sharing + chunked prefill
# ---------------------------------------------------------------------- #
def test_prefix_sharing_allocates_prefix_once_and_matches_generate(engine):
    """N requests with a common 12-token prefix must pay its pages and
    prefill FLOPs once (exactly 3 pages x 3 followers fewer allocations,
    ~1/N of the shared-span work) while decode stays token-identical and
    logits stay within 1e-6 of independent generate runs."""
    cfg = engine.cfg
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    suffixes = [2, 3, 4, 2]
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, k).astype(np.int32)])
        for k in suffixes
    ]
    reqs = [
        {"prompt": p, "max_new_tokens": 4, "rid": f"p{i}"}
        for i, p in enumerate(prompts)
    ]
    kw = dict(page_size=4, max_batch=4, record_logits=True)
    res_off, sched_off = engine.serve(reqs, **kw)
    res_on, sched_on = engine.serve(reqs, prefix_sharing=True, **kw)

    # footprint: page_size=4 -> the 12-token prefix is 3 pages, shared by
    # the 3 followers instead of re-allocated: exactly 9 pages saved
    assert sched_on.stats["prefix_hits"] == 3
    assert sched_on.stats["pages_shared"] == 9
    assert (
        sched_on.kv.allocator.total_allocated
        == sched_off.kv.allocator.total_allocated - 9
    )
    # prefill FLOPs for the shared span are skipped (12 tokens x 3)
    assert sched_on.stats["prefill_tokens"] == sched_off.stats["prefill_tokens"] - 36
    # the engine surfaces the counters
    assert engine.warmup_stats["prefix_hits"] == 3
    assert engine.warmup_stats["pages_shared"] == 9
    assert engine.warmup_stats["cow_copies"] == sched_on.stats["cow_copies"]

    for i, p in enumerate(prompts):
        ref = _reference(engine, p, 4)
        np.testing.assert_array_equal(res_on[f"p{i}"]["tokens"], ref)
        np.testing.assert_array_equal(res_off[f"p{i}"]["tokens"], ref)
        # per-step logits: sharing must stay within the 1e-6 contract
        got = sched_on.requests[f"p{i}"].logits
        want = sched_off.requests[f"p{i}"].logits
        assert len(got) == len(want) == 4
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def test_chunked_prefill_interleaves_with_decode_and_matches(engine):
    """A long prompt prefills in page-sized chunks across steps while the
    short requests keep decoding — and every output still matches the
    independent single-sequence reference."""
    cfg = engine.cfg
    prompts = _prompts(cfg, [14, 3, 4], seed=13)
    reqs = [
        {"prompt": p, "max_new_tokens": 5, "rid": f"c{i}", "arrival": float(i)}
        for i, p in enumerate(prompts)
    ]
    results, sched = engine.serve(
        reqs, page_size=4, max_batch=3, chunked_prefill=True, prefill_chunk=4,
        clock=_fake_clock(),
    )
    assert sched.stats["prefill_chunks"] >= 4 + 1 + 1  # 14/4 chunks + 2 shorts
    assert sched.stats["prefill_tokens"] == 14 + 3 + 4
    # interleaving: some step advanced the long prefill WHILE lanes decoded
    assert any(
        ev.get("prefill") and ev["running"] for ev in sched.transcript
    ), "chunked prefill never overlapped decode"
    for i, p in enumerate(prompts):
        ref = _reference(engine, p, 5)
        np.testing.assert_array_equal(results[f"c{i}"]["tokens"], ref)


def test_sharing_and_chunking_compose_under_page_pressure(engine):
    """Both features on with a pool small enough to force eviction: shared
    pages survive parking under their refcount, late chunk attachment picks
    up pages registered after admission, and everything stays lossless."""
    cfg = engine.cfg
    rng = np.random.default_rng(17)
    shared = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(0, cfg.vocab_size, k).astype(np.int32)])
        for k in (2, 3, 2, 4)
    ]
    reqs = [
        {"prompt": p, "max_new_tokens": 4, "rid": f"g{i}", "arrival": float(i)}
        for i, p in enumerate(prompts)
    ]
    reqs[1]["temperature"] = 0.8
    reqs[1]["rng"] = jax.random.PRNGKey(321)
    results, sched = engine.serve(
        reqs, page_size=4, max_batch=4, num_pages=11,
        prefix_sharing=True, chunked_prefill=True, prefill_chunk=8,
        clock=_fake_clock(),
    )
    assert sched.stats["prefix_hits"] >= 1
    assert sched.stats["pages_shared"] >= 3
    for i, p in enumerate(prompts):
        ref = _reference(
            engine, p, 4,
            temperature=reqs[i].get("temperature", 0.0),
            rng=jax.random.PRNGKey(321) if i == 1 else None,
        )
        np.testing.assert_array_equal(results[f"g{i}"]["tokens"], ref)
    assert sched.kv.allocator.num_free == sched.kv.allocator.num_pages


def test_pages_exhausted_mid_decode_evicts_and_resumes_lossless(engine, monkeypatch):
    """A typed PagesExhausted raised while a decode step's writes are
    resolved (growth/COW, before the device step) must evict per policy and
    keep every sequence lossless: the evicted lane takes no part in the
    step, and a lane that parks itself never splits its rng, so its redone
    step after resume samples identically."""
    from repro.serve.paged_cache import PagesExhausted

    cfg = engine.cfg
    prompts = _prompts(cfg, [5, 6], seed=77)
    sched = engine.make_scheduler(
        page_size=4, max_batch=2, num_pages=10, clock=_fake_clock()
    )
    for i, p in enumerate(prompts):
        sched.submit(
            p, max_new_tokens=6, temperature=0.7,
            rng=jax.random.PRNGKey(500 + i), rid=f"x{i}", arrival=float(i),
        )
    real = sched.kv.prepare_write
    fired = {}
    def flaky(rid, position):
        if rid == "x0" and position >= 7 and "x0" not in fired:
            # raised before the step: nothing of this step has run yet
            fired["x0"] = sched.stats["decode_steps_with_view"]
            raise PagesExhausted("forced mid-decode exhaustion")
        return real(rid, position)
    monkeypatch.setattr(sched.kv, "prepare_write", flaky)
    results = sched.run()
    assert fired, "the forced exhaustion never triggered"
    assert sched.stats["evictions"] >= 1
    # x1 (youngest) was evicted before the step, x0 decoded in it
    (ev,) = [e for e in sched.transcript if e["evicted"]][:1]
    assert ev["evicted"] == ["x1"] and "x1" in ev["running"]
    assert sched.stats["kv_parked_bytes"] > 0
    for i, p in enumerate(prompts):
        ref = _reference(
            engine, p, 6, temperature=0.7, rng=jax.random.PRNGKey(500 + i)
        )
        np.testing.assert_array_equal(results[f"x{i}"]["tokens"], ref)

    # single lane: nothing to evict -> the lane parks ITSELF before the
    # step (its rng never split) and redoes the step after resume with
    # identical sampling
    sched2 = engine.make_scheduler(
        page_size=4, max_batch=1, num_pages=8, clock=_fake_clock()
    )
    sched2.submit(
        prompts[0], max_new_tokens=6, temperature=0.7,
        rng=jax.random.PRNGKey(500), rid="solo",
    )
    real2 = sched2.kv.prepare_write
    fired2 = {}
    def flaky2(rid, position):
        if position >= 7 and not fired2:
            fired2["solo"] = sched2.stats["decode_steps_with_view"]
            raise PagesExhausted("forced self-park")
        return real2(rid, position)
    monkeypatch.setattr(sched2.kv, "prepare_write", flaky2)
    results2 = sched2.run()
    assert fired2 and sched2.stats["evictions"] >= 1
    # the step that parked its only lane dispatched nothing
    n_decode = sum(1 for e in sched2.transcript if e["running"])
    assert sched2.stats["decode_steps_with_view"] == n_decode - 1
    ref = _reference(
        engine, prompts[0], 6, temperature=0.7, rng=jax.random.PRNGKey(500)
    )
    np.testing.assert_array_equal(results2["solo"]["tokens"], ref)


def _step_arg_bytes(sched):
    """Host bytes a decode step puts on the device: the int32 page table,
    tokens, positions, keys, temperatures, write pages and 2 x 2B queued
    page copies (the model has no state leaves)."""
    B, VP = sched.max_batch, sched.kv.view_pages
    key = np.asarray(jax.random.PRNGKey(0)).nbytes
    return 4 * B * VP + 4 * B * 4 + B * key + 2 * 4 * 2 * B


def test_plain_decode_moves_no_kv(engine):
    """With the arenas on the device, a decode step uploads its page table
    and arguments and reads back no KV; prefill writes read none back."""
    cfg = engine.cfg
    prompts = _prompts(cfg, [3, 7, 5], seed=13)
    reqs = [
        {"prompt": p, "max_new_tokens": 6, "rid": f"n{i}"}
        for i, p in enumerate(prompts)
    ]
    results, sched = engine.serve(reqs, page_size=4, max_batch=4)
    st = sched.stats
    assert st["evictions"] == 0 and st["decode_steps_with_view"] > 0
    assert st["kv_h2d_bytes"] <= st["decode_steps_with_view"] * _step_arg_bytes(sched)
    assert st["kv_d2h_bytes"] == 0 and st["kv_parked_bytes"] == 0
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(results[f"n{i}"]["tokens"], _reference(engine, p, 6))


def test_forced_eviction_parks_kv_and_matches_generate(engine):
    """Page pressure parks private pages to the host and back — the one
    KV transfer left, counted in ``kv_parked_bytes`` — and sampled decode
    through it still matches independent generate runs token-for-token."""
    cfg = engine.cfg
    prompts = _prompts(cfg, [6, 6, 6], seed=29)
    reqs = [
        {"prompt": p, "max_new_tokens": 8, "rid": f"k{i}", "temperature": 0.8,
         "rng": jax.random.PRNGKey(40 + i)}
        for i, p in enumerate(prompts)
    ]
    results, sched = engine.serve(reqs, page_size=4, max_batch=3, num_pages=9)
    st = sched.stats
    assert st["evictions"] > 0 and st["resumes"] > 0
    page = sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize for a in sched.kv.arenas)
    assert st["kv_parked_bytes"] > 0 and st["kv_parked_bytes"] % page == 0
    assert st["kv_d2h_bytes"] + st["kv_h2d_bytes"] >= st["kv_parked_bytes"]
    for i, p in enumerate(prompts):
        ref = _reference(engine, p, 8, temperature=0.8, rng=jax.random.PRNGKey(40 + i))
        np.testing.assert_array_equal(results[f"k{i}"]["tokens"], ref)


def test_zero_page_stays_zero_with_inactive_lanes(engine):
    """Empty lanes' step writes are dropped, never aimed at the zero page
    that pads every table: it reads all zeros after every step."""
    cfg = engine.cfg
    sched = engine.make_scheduler(page_size=4, max_batch=4)
    for i, p in enumerate(_prompts(cfg, [5, 2], seed=3)):
        sched.submit(p, max_new_tokens=7 - 3 * i, rid=f"z{i}")
    saw_empty = False
    while sched.pending():
        sched.step()
        saw_empty |= any(r is None for r in sched.lanes)
        for a in sched.kv.arenas:
            assert not np.any(np.asarray(a[sched.kv.zero_page]))
    assert saw_empty
    assert sched.kv.arenas, "the model must have paged leaves"


def test_sharing_and_chunking_require_fully_paged_cache():
    """SSM/conv state summarizes the whole prefix: it can be neither
    inherited from shared pages nor rebuilt chunk-by-chunk, so the knobs
    must be rejected loudly for state-carrying models."""
    from repro.serve.scheduler import ContinuousBatchingScheduler

    cfg = get_config("mamba2-1.3b", reduced=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32", param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    for kw in ({"prefix_sharing": True}, {"chunked_prefill": True}):
        with pytest.raises(ValueError, match="fully-paged"):
            ContinuousBatchingScheduler(cfg, params, max_len=16, **kw)
    ContinuousBatchingScheduler(cfg, params, max_len=16)  # defaults stay fine


# ---------------------------------------------------------------------- #
# spans and counters of the serving step (repro.obs)
# ---------------------------------------------------------------------- #
def _traced_host_spans(tmp_path, work):
    """Run ``work()`` under a real profiler trace; return the program's host
    spans as (name, start, end, stats dict), in start order."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "kv.")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, child):
    """The innermost other span that encloses ``child``."""
    inside = [s for s in spans if s is not child and s[1] <= child[1] and child[2] <= s[2]]
    return min(inside, key=lambda s: s[2] - s[1])[0] if inside else None


def test_serving_step_spans_nest_and_count_the_view_bytes(engine, tmp_path):
    cfg = engine.cfg
    sched = engine.make_scheduler(page_size=4, max_batch=2)
    for i, p in enumerate(_prompts(cfg, [5, 3], seed=5)):
        sched.submit(p, max_new_tokens=5, rid=f"o{i}")
    sched.submit(_prompts(cfg, [4], seed=6)[0], max_new_tokens=2, rid="o2")
    sched.step()  # compile outside the trace
    before = dict(sched.stats)
    table_bytes = sched.kv.gather([None] * 2).nbytes
    arg_bytes = _step_arg_bytes(sched)
    spans = _traced_host_spans(tmp_path, sched.run)
    names = [s[0] for s in spans]

    decode_children = ("kv.gather", "kv.upload", "serve.lane_step", "serve.readback",
                       "kv.append")
    for s in spans:
        if s[0] in decode_children:
            assert _parent(spans, s) == "serve.decode", s
        elif s[0] in ("serve.decode", "serve.admit"):
            assert _parent(spans, s) == "serve.step", s
        elif s[0] in ("serve.prefill", "kv.write"):
            assert _parent(spans, s) == "serve.admit", s
        else:
            assert s[0] == "serve.step" and _parent(spans, s) is None, s
    n_decode = names.count("serve.decode")
    assert n_decode >= 3 and names.count("serve.prefill") == names.count("kv.write") == 1
    for child in decode_children:
        assert names.count(child) == n_decode, child
    assert names.count("serve.step") == sched.stats["steps"] - before["steps"]
    assert [s[3]["rid"] for s in spans if s[0] in ("serve.prefill", "kv.write")] == ["o2"] * 2

    # the upload is the page table and the step's arguments, byte for byte
    assert all(s[3]["bytes_h2d"] == arg_bytes for s in spans if s[0] == "kv.upload")
    assert all(s[3]["bytes"] == table_bytes for s in spans if s[0] == "kv.gather")
    steps = [s[3] for s in spans if s[0] == "serve.step"]
    assert [s[3]["lanes"] for s in spans if s[0] == "serve.decode"] == [
        st["running"] for st in steps if st["running"]]
    assert sum(st["admitted"] for st in steps) == 1

    # the scheduler's counters are the spans' arguments, summed
    def grew(key):
        return sched.stats[key] - before[key]

    assert grew("decode_steps_with_view") == n_decode
    assert grew("kv_h2d_bytes") == sum(s[3]["bytes_h2d"] for s in spans if s[0] == "kv.upload")
    assert grew("kv_d2h_bytes") == sum(
        s[3]["bytes_d2h"] for s in spans if s[0] == "kv.write") + sum(
        s[3]["kv_bytes"] for s in spans if s[0] == "serve.readback")
    # tokens and logits come back; KV stays on the device
    assert all(s[3]["bytes_d2h"] > 0 == s[3]["kv_bytes"] for s in spans if s[0] == "serve.readback")
    assert all(s[3]["bytes_d2h"] == 0 for s in spans if s[0] == "kv.write")


def test_serving_spans_are_recorded_in_chunked_prefill(engine, tmp_path):
    cfg = engine.cfg
    sched = engine.make_scheduler(page_size=4, max_batch=2, chunked_prefill=True,
                                  prefill_chunk=4)
    sched.submit(_prompts(cfg, [9], seed=3)[0], max_new_tokens=3, rid="c0")
    spans = _traced_host_spans(tmp_path, sched.run)
    chunks = [s for s in spans if s[0] == "serve.prefill"]
    assert [s[3]["tokens"] for s in chunks] == [4, 4, 1]
    assert all(_parent(spans, s) in ("serve.step", "serve.admit") for s in chunks)
    assert sched.stats["kv_h2d_bytes"] == sum(
        s[3]["bytes_h2d"] for s in spans if s[0] == "kv.upload")
    assert sched.stats["kv_d2h_bytes"] == sum(
        s[3]["bytes_d2h"] for s in spans if s[0] == "kv.write") + sum(
        s[3]["kv_bytes"] for s in spans if s[0] == "serve.readback")


def test_first_token_is_stamped_after_its_prefill(engine):
    """``first_token_at`` is read once the first token exists, after the
    step's opening clock reading, and never after the request finished."""
    cfg = engine.cfg
    readings = []
    clock = _fake_clock()

    def tick():
        readings.append(clock())
        return readings[-1]

    sched = engine.make_scheduler(page_size=4, max_batch=2, clock=tick)
    for i, p in enumerate(_prompts(cfg, [5, 3], seed=9)):
        sched.submit(p, max_new_tokens=3 - i, rid=f"f{i}", arrival=0.0)
    n = len(readings)
    sched.step()
    opening = readings[n]
    for rid in ("f0", "f1"):
        m = sched.requests[rid].metrics
        assert m["admitted_at"] == opening
        assert m["first_token_at"] > opening
    sched.run()
    for rid in ("f0", "f1"):
        m = sched.requests[rid].metrics
        assert m["first_token_at"] <= m["finished_at"]


def test_lane_step_program_has_a_stable_name(engine):
    sched = engine.make_scheduler(page_size=4, max_batch=2)
    assert sched._lane_step.__name__ == "serve_lane_step"
