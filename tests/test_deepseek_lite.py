"""DeepSeek-V2-Lite at a reduced size on the CPU, in float32 on seeded
random weights: served through the paged scheduler against the benchmark's
plain reference, its expert shares against the uncut layer, yarn against
its closed form, and the expert layer at decode with more tokens than the
capacity buckets hold."""
import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.deepseek_v2_lite import YARN, expert_share, full
from repro.models import decode_step, init_cache, init_params
from repro.models.attention import mla_scale
from repro.models.layers import rope, yarn_inv_freq, yarn_mscale
from repro.models.moe import moe_apply_counted, moe_init
from repro.serve.engine import ServeEngine

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.join(HERE, "..", "benchmarks", "chip")
KEY = jax.random.PRNGKey(7)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "deepseek_ref_under_test", os.path.join(CHIP, "references", "deepseek_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _f32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32")


def _file(held):
    """The benchmark's configuration file at ``reduced()``'s sizes, holding
    experts ``held = (first, count)`` of 32, in float32."""
    with open(os.path.join(CHIP, "configs", "deepseek-v2-lite-ep8.json")) as f:
        cfg = json.load(f)
    return dict(cfg, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
                moe_intermediate_size=32, n_routed_experts=held[1],
                published={"n_routed_experts": 32},
                held_experts={"first": held[0], "count": held[1]},
                precision={"weights": "float32", "compute": "float32"},
                program={"config": "repro.configs.deepseek_v2_lite:reduced",
                         "replace": {"param_dtype": "float32", "compute_dtype": "float32"}})


@pytest.mark.parametrize("held", [(0, 32), (8, 16)], ids=["all_experts", "experts_8_23"])
def test_serving_matches_the_reference_forward(held):
    """Prefill and decode through ServeEngine's scheduler (the MLA latent
    ``ckv`` and rope key ``kr`` paged in the device arenas) give the logits
    of the reference's full forward pass at every served position.  Both
    sides are float32; they differ in summation order (absorbed against
    materialised attention, one combine einsum against a loop over
    experts), ~1e-6 on logits of about unit size: 1e-4 leaves room for that
    and none for a wrong mask, rope, scale or expert."""
    file = _file(held)
    cfg = REF.program_config(file)
    assert cfg.moe.held == held and cfg.mla.q_lora_rank is None
    params = init_params(cfg, KEY)
    engine = ServeEngine(cfg, params, max_len=40)
    sched = engine.make_scheduler(page_size=4, max_batch=3, record_logits=True)
    assert [a.shape[-1] for a in sched.kv.arenas] == [16, 8, 16, 8]  # ckv, kr
    rng = np.random.default_rng(3)
    lens = {"a": (5, 7), "b": (11, 6), "c": (16, 9), "d": (7, 4)}  # one waits for a lane
    for rid, (p, n) in lens.items():
        sched.submit(rng.integers(0, 512, size=p, dtype=np.int32), n, rid=rid)
    sched.run()
    tokens_through = 0
    for rid, req in sched.requests.items():
        seq = np.concatenate([req.prompt, np.asarray(req.tokens[:-1], np.int32)])
        tokens_through += len(seq)
        vocab = np.arange(512, dtype=np.int32)[:, None, None] * np.ones((1, 1, len(seq)), np.int32)
        _, logits, _ = REF.scores(params, file, seq[None], vocab)  # (V, 1, S)
        want = logits[:, 0, req.prompt_len - 1:].T
        got = np.stack(req.logits)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    pairs = sched.stats["moe_pairs_here"]
    every = tokens_through * cfg.moe.top_k * 2  # two MoE layers
    assert pairs == every if held == (0, 32) else 0 < pairs < every


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of eight experts each: the parts their layers give, with
    the shared experts (which every chip computes alike) counted once, add
    up to the reference's uncut layer.  norm_topk_prob is false, so no
    share renormalises its own gates."""
    cfg = _f32(get_config("deepseek-v2-lite", reduced=True))
    assert not cfg.moe.norm_topk_prob and cfg.moe.held == (0, 32)
    p = moe_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, cfg.d_model))
    parts, pairs = [], 0
    for first in range(0, 32, 8):
        share = expert_share(cfg, first, 8)
        ps = {k: (v[first:first + 8] if k in ("w1", "w2", "w3") else v) for k, v in p.items()}
        y, _, n = moe_apply_counted(ps, x, share)
        parts.append(y)
        pairs += int(n)
    items = dict(REF.cfg_items(_file((0, 32))))
    shared = REF._swiglu(x, p["sw1"], p["sw3"], p["sw2"], False)
    uncut = REF.moe(x, p, items)
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5, rtol=0)
    y_all, _, n_all = moe_apply_counted(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_all), np.asarray(uncut), atol=2e-5, rtol=0)
    assert pairs == int(n_all) == 2 * 7 * cfg.moe.top_k


def test_yarn_matches_its_closed_form():
    dim, base = 64, 10_000.0
    inv, low, high = yarn_inv_freq(dim, base, YARN)
    assert (low, high) == (10, 23)
    i = np.arange(32)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base ** (-2 * i / dim) * (ramp / 40 + (1 - ramp))
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert yarn_mscale(40, 0.707) == pytest.approx(1.2608038, abs=5e-8)
    assert yarn_mscale(40, 0.707) / yarn_mscale(40, 0.707) == 1.0  # cos and sin as they are
    assert mla_scale(full()) == pytest.approx(0.1147214, abs=5e-8)
    assert mla_scale(full()) == pytest.approx(192 ** -0.5 * 1.2608038 ** 2, rel=1e-6)
    ref_inv, factor, scale, ref_low, ref_high = REF.yarn(
        json.load(open(os.path.join(CHIP, "configs", "deepseek-v2-lite-ep8.json"))))
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert (ref_low, ref_high, factor) == (10, 23, 1.0) and scale == pytest.approx(0.1147214)


def test_plain_rope_is_unchanged_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 3, 16))
    pos = jnp.arange(9)
    half = 8
    freqs = 1.0 / (500000.0 ** (np.arange(0, half, dtype=np.float32) / half))
    ang = pos[None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    before = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    assert np.array_equal(np.asarray(rope(x, pos, 500000.0)), np.asarray(before))
    yarn = np.asarray(rope(x, pos, 10_000.0, YARN))
    assert not np.allclose(yarn, np.asarray(rope(x, pos, 10_000.0)))


def test_decode_with_more_lanes_than_capacity_drops_no_held_pair():
    """32 lanes decode the same token: each of its experts gets 32 tokens,
    four times what a capacity bucket of the old path holds (8).  The held
    slice computes every one of them: every lane's logits are the one-lane
    result, and the pairs counted are 32 times the one lane's."""
    cfg = _f32(expert_share(get_config("deepseek-v2-lite", reduced=True), 0, 8))
    params = init_params(cfg, KEY)
    B = 32

    def step(b):
        cache = init_cache(cfg, b, 4, dtype=jnp.float32)
        tok = jnp.full((b, 1), 17, jnp.int32)
        return decode_step(params, cfg, tok, cache, jnp.int32(0), counts=True)

    one, _, n_one = step(1)
    many, _, n_many = step(B)
    assert int(n_one) > 0 and int(n_many) == B * int(n_one)
    np.testing.assert_allclose(np.asarray(many), np.broadcast_to(np.asarray(one), many.shape),
                               atol=1e-5, rtol=0)
    # the capacity buckets over all 32 experts hold 8 of the 32 tokens an
    # expert gets at this batch: that path drops the rest
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held=None))
    _, _, n_capped = decode_step(init_params(capped, KEY), capped,
                                 jnp.full((B, 1), 17, jnp.int32),
                                 init_cache(capped, B, 4, dtype=jnp.float32), jnp.int32(0),
                                 counts=True)
    assert int(n_capped) < B * cfg.moe.top_k * 2
    assert math.ceil(B * cfg.moe.top_k / 32 * cfg.moe.capacity_factor) == 8


@pytest.mark.parametrize("chunked", [False, True], ids=["whole_prefill", "chunked_prefill"])
def test_moe_pairs_reach_the_spans_and_the_stats(tmp_path, chunked):
    """Every prefill (whole or a chunk) and decode step puts the MoE pairs
    it computed on its span; ``moe_pairs_here`` is their sum.  With every
    expert held, a token makes top_k pairs in each of the two MoE layers."""
    import glob

    from jax.profiler import ProfileData

    cfg = _f32(get_config("deepseek-v2-lite", reduced=True))
    sched = ServeEngine(cfg, init_params(cfg, KEY), max_len=32).make_scheduler(
        page_size=4, max_batch=2, chunked_prefill=chunked)
    rng = np.random.default_rng(5)
    for rid, (p, n) in {"a": (6, 5), "b": (9, 4)}.items():
        sched.submit(rng.integers(0, 512, size=p, dtype=np.int32), n, rid=rid)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name in ("serve.prefill", "serve.decode")]
    per_token = cfg.moe.top_k * 2
    prefills = [s for n, s in spans if n == "serve.prefill"]
    decodes = [s for n, s in spans if n == "serve.decode"]
    assert len(prefills) == (3 if chunked else 2) and decodes  # chunks of 8
    assert all(s["moe_pairs"] == s["tokens"] * per_token for s in prefills)
    assert all(s["moe_pairs"] == s["lanes"] * per_token for s in decodes)
    assert sched.stats["moe_pairs_here"] == sum(s["moe_pairs"] for _, s in spans) == (
        (6 + 9 + 4 + 3) * per_token)
