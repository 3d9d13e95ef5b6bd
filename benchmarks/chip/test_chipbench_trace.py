"""The reduction from a profiler trace to device metrics."""
import json
import os

import pytest

import tracecalc
from tracecalc import Event

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def _toy():
    """A 100 ns window: ops at [10,30) and [20,40) overlap, [60,70) and one
    op straddling the window's end; two host spans cover the gaps."""
    return [
        _ev(HOST, "python", tracecalc.WINDOW_SPAN, 0, 100),
        _ev(HOST, "python", "staged.call", 5, 50),
        _ev(HOST, "python", "scheduler.step", 55, 40),
        _ev(DEV, tracecalc.OP_LINE, "fusion.1", 10, 20),
        _ev(DEV, tracecalc.OP_LINE, "gather.2", 20, 20),
        _ev(DEV, tracecalc.OP_LINE, "fusion.1", 60, 10),
        _ev(DEV, tracecalc.OP_LINE, "late", 95, 20),
        _ev(DEV, tracecalc.MODULE_LINE, "jit_fn", 10, 30),
        _ev(DEV, tracecalc.MODULE_LINE, "jit_bench_rescale", 60, 10),
        _ev(DEV, tracecalc.OP_LINE, "outside", 200, 10),
    ]


def test_busy_is_the_union_of_ops_inside_the_window():
    red = tracecalc.reduce(_toy(), ["staged.call", "scheduler.step"])
    # [10,40) + [60,70) + [95,100) = 45 ns of 100
    assert red["busy_s"] == pytest.approx(45e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert tracecalc.idle_percent(red) == pytest.approx(55.0)
    assert dict(red["device_ops"])["fusion.1"] == pytest.approx(30e-9)
    assert "outside" not in dict(red["device_ops"])
    assert red["modules"]["jit_fn"] == {"seconds": pytest.approx(30e-9), "count": 1}
    assert red["spans"] == {"staged.call": 1, "scheduler.step": 1}


def test_gaps_are_named_by_the_innermost_span_covering_them():
    red = tracecalc.reduce(_toy(), ["staged.call", "scheduler.step"])
    gaps = red["idle_gaps"]
    # [0,10) under staged.call, [40,60) mid 50 under staged.call,
    # [70,95) mid 82.5 under scheduler.step
    assert [g[0] for g in gaps] == ["scheduler.step", "staged.call", "staged.call"]
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 20e-9, 10e-9])
    bd = tracecalc.breakdown(red)
    assert bd["idle_gaps"][0] == ["scheduler.step", pytest.approx(25e-9)]
    assert len(bd["device_ops"]) <= tracecalc.TOP


def test_nothing_to_read_gives_none():
    assert tracecalc.reduce([e for e in _toy() if e.name != tracecalc.WINDOW_SPAN], []) is None
    assert tracecalc.reduce([e for e in _toy() if e.plane != DEV], []) is None
    assert tracecalc.idle_percent(None) is None


def test_recorded_chip_trace():
    """A trimmed trace of a staged SpMV window recorded on a TPU v5e."""
    with open(os.path.join(HERE, "fixtures", "trace_spmv.json")) as f:
        rec = json.load(f)
    w = rec["window"]
    events = [Event(w[0], w[1], w[2], 0.0, float(w[4]))] + [
        Event(rec["planes"][p], rec["lines"][li], name, float(t), float(d))
        for p, li, name, t, d in rec["events"]]
    red = tracecalc.reduce(events, ["staged.call"])
    assert red is not None and red["devices"] == 1
    assert 0.9 * red["window_s"] < red["busy_s"] <= red["window_s"]
    assert 0 <= tracecalc.idle_percent(red) < 10
    staged = {k: v for k, v in red["modules"].items() if not k.startswith("jit_bench_")}
    assert red["spans"]["staged.call"] == 1 and sum(v["count"] for v in staged.values()) == 2
    # the value gathers of the grouped backend take most of the device time
    top = red["device_ops"][0]
    assert top[0].endswith("fusion:kCustom") and top[1] > 1e-3
    assert red["idle_gaps"] and red["idle_gaps"][0][0] in ("staged.call", "none")


def test_live_cpu_trace_holds_the_window_and_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((64, 64))
    f(a).block_until_ready()
    tracer = tracecalc.Tracer(str(tmp_path / "tr"), True)
    with tracer.window():
        for _ in range(3):
            with tracecalc.span("staged.call"):
                f(a).block_until_ready()
    events = tracecalc.load_xplane(tracer.path)
    names = [e.name for e in events]
    assert names.count("staged.call") == 3 and tracecalc.WINDOW_SPAN in names
    # the CPU has no device plane: nothing to read, and no number made up
    assert tracecalc.reduce(events, ["staged.call"]) is None
    assert tracecalc.summarize_planes(events)
