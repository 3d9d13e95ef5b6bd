"""The DeepSeek-V2-Lite cell rehearsed on the CPU at a tiny size through the
harness and the unchanged closed-loop driver: a sound run is ``correct``;
a run whose program renormalises the top-k gates, holds the wrong slice of
experts or uses plain rope is not, nor is the float8 control.  Also the
configuration's checks, its work count and the two scope-share readers."""
import dataclasses
import os
import time

import pytest

import harness
import scopecalc
import spancalc
import tracecalc
from spancalc import Rec

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELL = "deepseek-v2-lite-ep8.batch2k"


@pytest.fixture(autouse=True)
def plan_cache(tmp_path, monkeypatch):
    from repro.core.cache import set_default_cache

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "plans"))
    set_default_cache(None)
    yield
    set_default_cache(None)


def tiny_cell() -> harness.Cell:
    """The cell at ``deepseek_v2_lite.reduced()``'s sizes: 3 layers (one
    dense), 32 routed experts of which 8 are held, top-6.  In float32: at
    this size bf16 rounding of the hidden state moves the served logits by
    about as much as the planted faults do."""
    cell = harness.load_cell(CELL, BENCH)
    f32 = {"param_dtype": "float32", "compute_dtype": "float32"}
    cfg = dict(cell.config, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
               moe_intermediate_size=32, published={"n_routed_experts": 32},
               precision={"weights": "float32", "compute": "float32"},
               program={"config": "repro.configs.deepseek_v2_lite:reduced",
                        "replace": dict(cell.config["program"]["replace"], **f32)})
    tr = dict(cell.traffic, clients=4, max_batch=4, page_size=4, max_len=40,
              prompt_lens=[8, 8, 16], output_lens=[3, 5, 8], rounds=8, check_requests=8)
    # between the two readings at this size: sound runs 0 (every served
    # token the reference's first), the float8 control at least 0.337, over
    # 12 seeds (2**33 + 3 + 7 i, 2 s windows); the planted faults read
    # 0.017-3.3 on the first four
    return dataclasses.replace(cell, config=cfg, traffic=tr, limits={"max_logit_gap": 0.01})


def drive(cell, seed=2**33 + 3, seconds=2.0, fault=None, control=False):
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                          tracer=tracecalc.Tracer("", False),
                          t_process=time.perf_counter(), fault=fault, control=control)
    out = cell.driver().run(ctx)
    return out, harness.result(ctx, out, None)


def test_deepseek_cell_is_correct():
    out, line = drive(tiny_cell())
    assert line["correct"], line["checks"]
    # a saturated closed loop: throughput is its end-to-end metric; the ITL
    # tail is logged, not reported (see PERF.md, section 2)
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(v["value"] and v["value"] > 0 for v in line["metrics"].values())
    c = out.counters
    assert c["sched_decode_tokens"] == c["decode_tokens"] > 0


def _program_with(change):
    """A fault: the scheduler's prefill and lane step rebuilt for the
    program config that ``change`` makes, over the same weights and pages."""
    def plant(sched):
        from repro.serve.scheduler import ContinuousBatchingScheduler

        other = ContinuousBatchingScheduler(
            change(sched.cfg), sched.params, max_len=sched.max_len,
            page_size=sched.kv.page_size, max_batch=sched.max_batch)
        sched._prefill, sched._lane_step = other._prefill, other._lane_step
    return plant


def _moe(**kw):
    return lambda cfg: dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))


FAULTS = {
    "renormalised_gates": _moe(norm_topk_prob=True),
    "slice_shifted": _moe(held=(8, 8)),
    "plain_rope": lambda cfg: dataclasses.replace(cfg, rope_scaling=None),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_deepseek_faults_are_not_correct(fault):
    _, line = drive(tiny_cell(), fault=_program_with(FAULTS[fault]))
    assert not line["correct"], line["checks"]


def test_deepseek_control_is_not_correct():
    out, line = drive(tiny_cell(), control=True)
    value, limit = out.checks["max_logit_gap"]
    assert not line["correct"] and value > limit >= out.counters["program_gap"]


@pytest.mark.parametrize("change", [
    {"hidden_size": 4096},
    {"held_experts": {"first": 60, "count": 8}},
    {"n_routed_experts": 16},
    {"norm_topk_prob": True},
    {"q_lora_rank": 1536},
    {"num_experts_per_tok": 8},
    {"rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 0.707,
                      "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                      "type": "yarn"}},
    {"topk_method": "group_limited_greedy"},
], ids=["width", "held_slice", "held_count", "norm_topk_prob", "q_lora", "top_k",
        "yarn_factor", "topk_method"])
def test_deepseek_program_config_raises_on_a_departure(change):
    cell = harness.load_cell(CELL, BENCH)
    with pytest.raises(ValueError):
        cell.reference().program_config(dict(cell.config, **change))


def test_deepseek_program_holds_its_share_at_the_published_sizes():
    from repro.models.config import param_count

    cell = harness.load_cell(CELL, BENCH)
    ref = cell.reference()
    mcfg = ref.program_config(cell.config)
    assert mcfg.moe.held == (0, 8) and mcfg.moe.num_experts == 64
    assert mcfg.mla.q_lora_rank is None and not mcfg.moe.norm_topk_prob
    assert param_count(mcfg) == 3_110_975_488  # the leaves less 27 latent norms
    assert ref.work(cell.config) == (2_121_531_392, 276_480, 419_430_400)
    inv, factor_cs, scale, low, high = ref.yarn(cell.config)
    assert (low, high, factor_cs) == (10, 23, 1.0)
    assert scale == pytest.approx(0.1147214, abs=5e-8)


# ---------------------------------------------------------------------- #
# the scope shares, on toy traces and a recorded one
# ---------------------------------------------------------------------- #
DEV = "/device:TPU:0"


def _op(name, start, dur, tf_op):
    return Rec(DEV, tracecalc.OP_LINE, name, float(start), float(dur),
               ((spancalc.OP_NAME_STAT, tf_op),))


def _toy():
    lane, pre = "jit(serve_lane_step)/vmap(while)/body", "jit(serve_prefill)/while/body"
    win = Rec("/host:CPU", "python3", tracecalc.WINDOW_SPAN, 0.0, 1000.0)
    return [win,
            _op("while.1", 0, 100, "jit(serve_lane_step)/vmap(while)"),  # holds the next four
            _op("fusion.1", 0, 30, lane + "/moe.experts/dot_general"),
            _op("fusion.2", 30, 10, lane + "/moe.route/top_k"),
            _op("fusion.3", 40, 20, lane + "/mla.attend/dot_general"),
            _op("fusion.4", 60, 40, lane + "/dot_general"),
            _op("fusion.5", 200, 50, pre + "/moe.shared/dot_general"),
            _op("fusion.6", 250, 50, pre + "/mla.attend/dot_general"),
            _op("fusion.7", 400, 500, "jit(other)/dot_general")]  # not a serving program


def test_scope_shares_count_leaf_ops_of_the_serving_programs():
    moe = harness.load_module(os.path.join(harness.BENCH_DIR, "metrics", "moe_share.serve.py"))
    mla = harness.load_module(os.path.join(harness.BENCH_DIR, "metrics", "mla_share.serve.py"))
    recs = spancalc.in_window(_toy())
    assert len(scopecalc.leaf_ops(recs)) == 7
    times = scopecalc.scope_times(recs, moe.SCOPES)
    assert times[("decode", "moe.experts")] == 30 and times[("prefill", "moe.shared")] == 50
    assert scopecalc.share(times) == pytest.approx(100 * 90 / 200)
    assert scopecalc.share(scopecalc.scope_times(recs, mla.SCOPES)) == pytest.approx(35.0)


def test_scope_shares_read_nothing_without_scopes():
    recs = spancalc.in_window([dataclasses.replace(r, stats=()) if r.dur_ns < 1000 else r
                               for r in _toy()])
    assert scopecalc.share(scopecalc.scope_times(recs, ("moe.experts",))) is None
    assert scopecalc.scope_times(None, ("moe.experts",)) is None


def test_recorded_granite_step_has_no_moe_or_mla_scopes():
    from test_chipbench_spans import _fixture

    recs = spancalc.in_window(_fixture("trace_serve_step.json"))
    times = scopecalc.scope_times(recs, ("moe.route", "moe.experts", "moe.shared",
                                         "mla.attend"))
    assert times and set(s for _, s in times) == {None}
    assert scopecalc.share(times) is None
