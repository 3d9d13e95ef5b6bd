"""The readers of the program's spans, counters and scopes (``spancalc``)."""
import dataclasses
import os
import types

import pytest

import harness
import spancalc
import tracecalc
from spancalc import Rec

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
HOST = "/host:CPU"
MB = 717_225_984  # 8 lanes x 608 positions x 36 layers x (K, V) x 8 heads x 128 x 2 B


def _h(name, start, dur, **stats):
    return Rec(HOST, "python3", name, float(start), float(dur), tuple(stats.items()))


def _op(name, start, dur, op_name=""):
    stats = (("tf_op", op_name),) if op_name else ()
    return Rec(DEV, tracecalc.OP_LINE, name, float(start), float(dur), stats)


def _serving():
    """A 1000 ns window with two scheduler steps; the second decodes."""
    return [
        _h(tracecalc.WINDOW_SPAN, 0, 1000),
        _h("scheduler.step", 10, 300),
        _h("serve.step", 12, 296, running=0, admitted=1),
        _h("serve.admit", 14, 290),
        _h("serve.prefill", 20, 200, rid="r1", tokens=128),
        _h("kv.write", 220, 80, rid="r1", bytes_d2h=4096),
        _h("scheduler.step", 400, 590),
        _h("serve.step", 402, 586, running=8, admitted=0),
        _h("serve.decode", 410, 570, lanes=8),
        _h("kv.gather", 410, 200, bytes=MB),
        _h("kv.upload", 610, 100, bytes_h2d=MB),
        _h("serve.lane_step", 710, 60),
        _h("serve.readback", 770, 20, bytes_d2h=300, kv_bytes=200),
        _h("kv.append", 790, 180, lanes=8),
        _h("lower_sharding_computation", 500, 5),
        _h("lower_sharding_computation", 1500, 5),  # after the window
        _op("fusion.1", 100, 100),  # under the prefill
        _op("fusion.2", 720, 40),  # the lane step
    ]


def test_kv_readers_divide_by_decode_steps():
    recs = spancalc.in_window(_serving())
    assert spancalc.decode_steps(recs) == 1
    # gather 200 + upload 100 + append 180 + write 80 = 560 ns
    assert spancalc.kv_host_ms(recs) == pytest.approx(560e-6)
    assert spancalc.kv_h2d_mb(recs) == pytest.approx(MB / 1e6)
    assert spancalc.compiles(recs) == 1


def test_window_clips_and_drops_events_outside():
    recs = spancalc.in_window(_serving() + [_h("kv.gather", 990, 50, bytes=1)])
    last = [r for r in recs if r.name == "kv.gather"][-1]
    assert (last.start_ns, last.dur_ns) == (990, 10)
    assert [r.name for r in recs].count(tracecalc.WINDOW_SPAN) == 1
    assert spancalc.in_window([e for e in _serving() if e.name != tracecalc.WINDOW_SPAN]) is None


def test_coverage_by_innermost_span():
    cov = spancalc.coverage(spancalc.in_window(_serving()))
    step = cov["scheduler.step"]
    assert step["seconds"] == pytest.approx(890e-9)
    # uncovered: [10,12) + [308,310) + [400,402) + [988,990) = 8 ns
    assert step["covered"] == pytest.approx(1 - 8 / 890)
    assert step["by_span"]["kv.gather"] == pytest.approx(200 / 890)
    idle = cov["device_idle"]
    # busy [100,200) and [720,760): idle 860 ns
    assert idle["seconds"] == pytest.approx(860e-9)
    by = idle["by_span"]
    assert by["serve.lane_step"] == pytest.approx(20 / 860)
    assert by["serve.prefill"] == pytest.approx(100 / 860)
    # outside every serve.step: [0,12) [308,402) [988,1000)
    assert by["none"] == pytest.approx((12 + 94 + 12) / 860)
    assert spancalc.format_coverage(cov)[0].startswith("spans: scheduler.step")


def test_pack_share_by_innermost_scope_without_bench_programs():
    recs = [
        _h(tracecalc.WINDOW_SPAN, 0, 1000),
        Rec(DEV, tracecalc.MODULE_LINE, "jit_sable_spmv_grouped(1)", 0, 600),
        Rec(DEV, tracecalc.MODULE_LINE, "jit_bench_rescale(2)", 700, 100),
        _op("fusion.1", 0, 300, "jit(sable_spmv_grouped)/sable.pack/gather"),
        # a pack nested in a multiply counts as pack (the innermost scope)
        _op("fusion.2", 300, 200, "jit(sable_spmv_grouped)/sable.multiply/sable.pack/gather"),
        _op("fusion.3", 500, 100, "jit(sable_spmv_grouped)/sable.multiply/dot_general"),
        _op("fusion.4", 720, 60, "jit(bench_rescale)/div"),  # left out
    ]
    w = spancalc.in_window(recs)
    assert spancalc.pack_share(w) == pytest.approx(100 * 500 / 600)
    assert spancalc.scope_time(w) == {"sable.pack": 500.0, "sable.multiply": 100.0}


def _pb(*fields):
    """Protobuf wire bytes of (field number, int or bytes or str) pairs."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_come_from_the_event_metadata():
    """A device op's ``op_name`` is the ``tf_op`` stat of its event
    metadata, given as a string or as a reference to a stat name."""
    stat_meta = [_pb((1, k), (2, _pb((1, k), (2, name))))
                 for k, name in ((7, "tf_op"), (8, "flops"), (9, "jit(g)/sable.multiply/dot"))]
    event_meta = [
        _pb((1, 1), (2, _pb((1, 1), (2, "%fusion.1 = f32[4] fusion()"),
                             (5, _pb((1, 8), (4, 12))),
                             (5, _pb((1, 7), (5, "jit(f)/sable.pack/gather")))))),
        _pb((1, 2), (2, _pb((1, 2), (2, "%dot.2 = f32[4] dot()"), (5, _pb((1, 7), (7, 9)))))),
        _pb((1, 3), (2, _pb((1, 3), (2, "%copy.3 = f32[4] copy()")))),
    ]
    device = _pb((1, 1), (2, DEV), (3, _pb((1, 5), (2, "XLA Ops"))),
                 *[(4, m) for m in event_meta], *[(5, m) for m in stat_meta])
    space = _pb((1, _pb((1, 2), (2, HOST), *[(5, m) for m in stat_meta[:1]])), (1, device))
    names = spancalc.op_names(space)
    assert names == {DEV: {"%fusion.1 = f32[4] fusion()": "jit(f)/sable.pack/gather",
                           "%dot.2 = f32[4] dot()": "jit(g)/sable.multiply/dot"}}
    op = Rec(DEV, tracecalc.OP_LINE, "%fusion.1", 0, 1, (("tf_op", names[DEV][
        "%fusion.1 = f32[4] fusion()"]),))
    assert spancalc.op_scope(op) == "sable.pack"
    assert spancalc.op_scope(dataclasses.replace(op, stats=())) is None


def test_nothing_to_read_gives_none():
    bare = [e for e in _serving() if not spancalc.is_program_span(e)]
    w = spancalc.in_window(bare)
    assert spancalc.kv_host_ms(w) is None and spancalc.kv_h2d_mb(w) is None
    assert spancalc.coverage(w) is None
    assert spancalc.pack_share(spancalc.in_window([_h(tracecalc.WINDOW_SPAN, 0, 10),
                                                   _op("fusion.1", 0, 5)])) is None
    assert spancalc.compiles(None) is None
    assert spancalc.window_records(None) is None


@pytest.mark.parametrize("name", ["kv_host_ms.serve", "kv_h2d_mb.serve", "compiles.window.serve",
                                  "compiles.window.sparse", "staged_pack_share"])
def test_readers_read_none_without_a_trace(name):
    reader = harness.load_module(os.path.join(HERE, "metrics", name + ".py"))
    run = types.SimpleNamespace(outcome=types.SimpleNamespace(trace_path=None))
    assert reader.read(run) is None


def test_live_cpu_trace_keeps_span_arguments(tmp_path):
    import jax
    import jax.numpy as jnp

    x = jnp.ones(3)
    tracer = tracecalc.Tracer(str(tmp_path / "tr"), True)
    with tracer.window():
        with jax.profiler.TraceAnnotation("serve.decode", lanes=2):
            with jax.profiler.TraceAnnotation("kv.upload", bytes_h2d=4096):
                jax.jit(lambda a: a + 1)(x).block_until_ready()
    recs = spancalc.window_records(tracer.path)
    assert spancalc.kv_h2d_mb(recs) == pytest.approx(4096e-6)
    up = [r for r in recs if r.name == "kv.upload"]
    assert up[0].stat("bytes_h2d") == 4096
    # the jit above compiled inside the window
    assert spancalc.compiles(recs) == 1


def _fixture(name):
    """A recorded chip trace cut by hand (see its ``source``), as records."""
    import json

    with open(os.path.join(HERE, "fixtures", name)) as f:
        doc = json.load(f)
    p, li, n, t, d = doc["window"]
    recs = [Rec(doc["planes"][p], doc["lines"][li], n, float(t), float(d))]
    for p, li, n, t, d, s in doc["events"]:
        stats = tuple(doc["stats"][s].items()) if s >= 0 else ()
        recs.append(Rec(doc["planes"][p], doc["lines"][li], doc["names"][n], float(t), float(d),
                        stats))
    return recs


def test_recorded_serving_step():
    """One scheduler step of granite-8b-sable.batch on a TPU v5e: it admits
    a 256-token request and decodes 8 lanes over a 608-position view."""
    recs = spancalc.in_window(_fixture("trace_serve_step.json"))
    assert spancalc.decode_steps(recs) == 1
    assert spancalc.kv_h2d_mb(recs) == pytest.approx(MB / 1e6)
    assert spancalc.kv_host_ms(recs) > 1000  # the host's view build dominates the step
    assert spancalc.compiles(recs) == 0
    (step,) = spancalc.host_spans(recs, ["serve.step"])
    assert step.stat("running") == 8 and step.stat("admitted") == 1
    (decode,) = spancalc.host_spans(recs, ["serve.decode"])
    for name in ("kv.gather", "kv.upload", "serve.lane_step", "serve.readback", "kv.append"):
        (s,) = spancalc.host_spans(recs, [name])
        assert decode.start_ns <= s.start_ns and s.end_ns <= decode.end_ns, name
    (write,) = spancalc.host_spans(recs, ["kv.write"])
    (prefill,) = spancalc.host_spans(recs, ["serve.prefill"])
    assert write.stat("rid") == prefill.stat("rid") and prefill.stat("tokens") == 256
    # K and V of 36 layers x 256 positions x 8 heads x 128, bf16
    assert write.stat("bytes_d2h") == 36 * 2 * 256 * 8 * 128 * 2
    cov = spancalc.coverage(recs)
    assert cov["scheduler.step"]["covered"] > 0.99
    assert cov["device_idle"]["covered"] > 0.95
    assert max(cov["device_idle"]["by_span"].items(), key=lambda kv: kv[1])[0] == "kv.gather"


def test_recorded_staged_call_scopes():
    """The first 30 ms of paper-nu-10k.spmv on a TPU v5e: the grouped
    backend's value gathers, under ``sable.pack``, take nearly all of it."""
    recs = spancalc.in_window(_fixture("trace_spmv_scopes.json"))
    t = spancalc.scope_time(recs)
    assert t["sable.pack"] > 10 * t["sable.multiply"] > 0
    assert spancalc.pack_share(recs) > 95
    assert spancalc.kv_host_ms(recs) is None and spancalc.coverage(recs) is None
    assert spancalc.compiles(recs) == 0
    # a trace of the program before it named its scopes reads nothing
    bare = [dataclasses.replace(r, stats=()) for r in recs]
    assert spancalc.pack_share(bare) is None


def test_recorded_serving_step_without_program_spans_reads_none():
    recs = [r for r in spancalc.in_window(_fixture("trace_serve_step.json"))
            if not spancalc.is_program_span(r)]
    assert spancalc.kv_host_ms(recs) is None and spancalc.kv_h2d_mb(recs) is None
    assert spancalc.coverage(recs) is None and spancalc.pack_share(recs) is None
    assert spancalc.compiles(recs) == 0
