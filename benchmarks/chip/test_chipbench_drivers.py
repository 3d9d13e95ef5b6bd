"""Rehearsals of every driver on the CPU at tiny sizes: a sound run is
``correct``; the same run with its timed path broken underneath, or with the
control in the program's place, is not."""
import dataclasses
import os
import time

import numpy as np
import pytest

import faults
import harness
import tracecalc

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


@pytest.fixture(autouse=True)
def plan_cache(tmp_path, monkeypatch):
    from repro.core.cache import set_default_cache
    from repro.core.staging import clear_cache

    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "plans"))
    set_default_cache(None)
    yield
    set_default_cache(None)
    clear_cache()


def tiny_sparse(traffic: str) -> harness.Cell:
    cell = harness.load_cell(f"paper-nu-10k.{traffic}", BENCH)
    # 24 of 36 blocks: every row reaches every column, as in the paper's
    # matrix.  With fewer, power iteration drives some entries of x through
    # float32's subnormal range, where the device flushes them to zero.
    cfg = dict(cell.config, rows=300, cols=300, row_splits=6, col_splits=6, num_blocks=24)
    tr = dict(cell.traffic, n_cols=8) if cell.traffic["kind"] == "spmm" else cell.traffic
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def tiny_serve() -> harness.Cell:
    cell = harness.load_cell("granite-8b-sable.batch", BENCH)
    cfg = dict(cell.config, hidden_size=96, intermediate_size=256, num_hidden_layers=3,
               num_attention_heads=6, num_key_value_heads=2, head_dim=16, vocab_size=512,
               sable={"block_m": 32, "block_n": 32, "density": 0.5, "target": "ffn", "seed": 0},
               program={"config": "repro.configs.granite_8b:reduced",
                        "replace": cell.config["program"]["replace"]})
    tr = dict(cell.traffic, clients=2, max_batch=2, page_size=4, max_len=40,
              prompt_lens=[8, 8, 16], output_lens=[3, 5, 8], rounds=8, check_requests=8)
    # the tiny model's logits spread less than the full one's, and its float8
    # control moves them less: a tighter limit, between the two readings
    # (sound runs at most 0.011, the control at least 0.041, over 12 seeds)
    return dataclasses.replace(cell, config=cfg, traffic=tr, limits={"max_logit_gap": 0.025})


def tiny_dense() -> harness.Cell:
    """``tiny_serve`` with no ``sable``: granite's reduced sizes with the full
    FFN matrices, compared against the reference's dense branch."""
    cell = tiny_serve()
    cfg = {k: v for k, v in cell.config.items() if k != "sable"}
    # between the two readings: sound runs at most 0.0067, the float8
    # control at least 0.047, over 12 seeds (2**33 + 3 + 7 i, 2 s windows)
    return dataclasses.replace(cell, config=cfg, limits={"max_logit_gap": 0.02})


def drive(cell, seed=2**33 + 3, seconds=0.5, fault=None, control=False):
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                          tracer=tracecalc.Tracer("", False),
                          t_process=time.perf_counter(), fault=fault, control=control)
    out = cell.driver().run(ctx)
    return out, harness.result(ctx, out, None)


@pytest.mark.parametrize("traffic", ["spmv", "spmm128"])
def test_sparse_cell_is_correct(traffic):
    out, line = drive(tiny_sparse(traffic))
    assert line["correct"] and line["attempted"] > 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"call_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu" and list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.BY_DRIVER["staged_loop"]), ids=lambda n: "_" + n)
@pytest.mark.parametrize("traffic", ["spmv", "spmm128"])
def test_sparse_faults_are_not_correct(traffic, fault):
    _, line = drive(tiny_sparse(traffic), fault=faults.BY_DRIVER["staged_loop"][fault])
    assert not line["correct"]


@pytest.mark.parametrize("traffic", ["spmv", "spmm128"])
def test_sparse_control_is_not_correct(traffic):
    out, line = drive(tiny_sparse(traffic), control=True)
    value, limit = out.checks["max_rel_err"]
    assert not line["correct"] and value > limit


def test_serving_cell_is_correct():
    out, line = drive(tiny_serve(), seconds=2.0)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] and v["value"] > 0 for v in line["metrics"].values())
    c = out.counters
    assert c["sched_decode_tokens"] == c["decode_tokens"] > 0
    assert c["tokens_through_layers"] > c["decode_tokens"]


@pytest.mark.parametrize("fault", sorted(faults.BY_DRIVER["closed_loop"]), ids=lambda n: "_" + n)
def test_serving_faults_are_not_correct(fault):
    _, line = drive(tiny_serve(), seconds=2.0, fault=faults.BY_DRIVER["closed_loop"][fault])
    assert not line["correct"], line["checks"]


def test_serving_control_is_not_correct():
    out, line = drive(tiny_serve(), seconds=2.0, control=True)
    value, limit = out.checks["max_logit_gap"]
    assert not line["correct"] and value > limit >= out.counters["program_gap"]


def test_dense_serving_cell_is_correct():
    out, line = drive(tiny_dense(), seconds=2.0)
    assert line["correct"], line["checks"]
    assert all(v["value"] and v["value"] > 0 for v in line["metrics"].values())
    # the three full FFN matrices are counted, not tiles
    attn = 96 * 6 * 16 + 2 * 96 * 2 * 16 + 6 * 16 * 96
    assert out.counters["flops"][0] == 2 * 3 * (attn + 3 * 96 * 256)


def test_dense_serving_control_is_not_correct():
    out, line = drive(tiny_dense(), seconds=2.0, control=True)
    value, limit = out.checks["max_logit_gap"]
    assert not line["correct"] and value > limit >= out.counters["program_gap"]


def _record_scheduler(monkeypatch) -> list:
    """Keyword arguments of every ``make_scheduler`` call, and its scheduler."""
    from repro.serve.engine import ServeEngine

    made = []
    real = ServeEngine.make_scheduler

    def spy(self, **kw):
        sched = real(self, **kw)
        made.append((kw, sched))
        return sched
    monkeypatch.setattr(ServeEngine, "make_scheduler", spy)
    return made


def test_traffic_scheduler_keys_reach_the_scheduler(monkeypatch):
    made = _record_scheduler(monkeypatch)
    cell = tiny_serve()
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  scheduler={"prefix_sharing": True}))
    _, line = drive(cell, seconds=2.0)
    ((kw, sched),) = made
    assert kw == {"page_size": 4, "max_batch": 2, "prefix_sharing": True}
    assert sched.prefix_sharing and line["correct"], line["checks"]


def test_traffic_without_scheduler_key_builds_it_as_before(monkeypatch):
    made = _record_scheduler(monkeypatch)
    cell = tiny_serve()
    assert "scheduler" not in cell.traffic

    class Built(Exception):
        pass

    def stop(sched):
        raise Built
    with pytest.raises(Built):
        drive(cell, fault=stop)
    ((kw, sched),) = made
    assert kw == {"page_size": 4, "max_batch": 2}
    assert not sched.prefix_sharing and not sched.chunked_prefill


def test_requests_are_the_same_work_for_every_seed():
    cell = harness.load_cell("granite-8b-sable.batch", BENCH)
    drv = cell.driver()
    a = drv.make_requests(cell.traffic, 49152, 2**33 + 1)
    b = drv.make_requests(cell.traffic, 49152, 2**34 + 5)
    assert sorted((len(p), n) for p, n in a) != [] and len(a) == len(b)
    for r in range(cell.traffic["rounds"]):
        ra, rb = a[10 * r:10 * r + 10], b[10 * r:10 * r + 10]
        assert sorted(len(p) for p, _ in ra) == sorted(len(p) for p, _ in rb)
        assert sorted(n for _, n in ra) == sorted(n for _, n in rb)
    assert any(not np.array_equal(pa, pb) for (pa, _), (pb, _) in zip(a, b))


@pytest.mark.parametrize("make", [lambda: tiny_sparse("spmv"), tiny_serve])
def test_control_runner_reads_program_and_control(make):
    import control

    cell = make()
    (check,) = cell.limits
    r = control.readings(cell, [2**33 + 9], 1.0)
    assert len(r["program"]) == len(r["control"]) == 1
    assert r["program"][0] <= cell.limits[check] < r["control"][0]


def test_control_runner_reads_planted_faults():
    import control

    cell = tiny_sparse("spmv")
    (value,) = control.fault_readings(cell, [2**33 + 11], 0.5, "half_left_out")
    assert value > cell.limits["max_rel_err"]
