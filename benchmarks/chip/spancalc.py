"""The program's own spans, counters and scopes, read from a traced window.

``tracecalc`` reduces a trace to device time; this module reads what the
program under test writes into the same trace (see ``repro.obs``):

* host spans (``serve.step``, ``kv.gather``, ...) with their arguments,
  which the profiler keeps as event stats;
* the ``sable.pack`` / ``sable.multiply`` scopes of the staged programs,
  which reach each device op through its ``op_name``;
* the programs JAX builds (each then compiled, or loaded from the
  compile cache).

Records are plain :class:`Rec` tuples, clipped to the benchmark's
``bench.window`` span, so the functions below are pure and the tests can
feed them toy or recorded events.  Every reader returns None when the
trace holds nothing for it to read.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Optional

import tracecalc

#: host spans the program writes (``repro.obs``)
PROGRAM_PREFIXES = ("serve.", "kv.")
#: spans whose time is the host's KV work
KV_HOST_SPANS = ("kv.gather", "kv.upload", "kv.append", "kv.write")
#: JAX's host event for each new program it builds, which is then compiled
#: (``backend_compile_and_load``) or loaded from the compile cache (on a TPU
#: only ``TpuClient::LoadInternal`` shows it): one event a program either way
PROGRAM_BUILD_EVENT = "lower_sharding_computation"
#: the staged programs' named scopes
PACK, MULTIPLY = "sable.pack", "sable.multiply"
#: the stat of a device op's event metadata that holds its ``op_name``
OP_NAME_STAT = "tf_op"


@dataclasses.dataclass(frozen=True)
class Rec:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()  # ((key, value), ...)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def stat(self, key: str, default=None):
        for k, v in self.stats:
            if k == key:
                return v
        return default


def _stat_value(v):
    return v if isinstance(v, (int, float, str)) else str(v)


@functools.lru_cache(maxsize=1)
def load(path: str) -> tuple[Rec, ...]:
    """Every event of an ``.xplane.pb`` with its stats, device op names kept
    whole; a device op also gets its ``op_name`` as the ``tf_op`` stat,
    which the trace keeps with the op's metadata (``op_names``)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        op_name = op_names(f.read())
    out = []
    for plane in ProfileData.from_file(path).planes:
        names = op_name.get(plane.name, {})
        for line in plane.lines:
            for e in line.events:
                stats = tuple((str(k), _stat_value(v)) for k, v in e.stats)
                if e.name in names:
                    stats += ((OP_NAME_STAT, names[e.name]),)
                out.append(Rec(plane.name, line.name, e.name, float(e.start_ns),
                               float(e.duration_ns), stats))
    return tuple(out)


# ---------------------------------------------------------------------- #
# the op_name of device ops: ``jax.profiler.ProfileData`` gives an event's
# own stats but not those of its metadata, where the device's trace keeps
# ``tf_op``; so the XSpace protobuf (tsl/profiler/protobuf/xplane.proto) is
# read here for just that, with its field numbers
# ---------------------------------------------------------------------- #
def _varint(buf, pos: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one message: varints as ints, and
    length-delimited fields as (start, end) offsets into ``buf``."""
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            v, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            v, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {pos}")
        yield num, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(data: bytes) -> dict:
    """{device plane: {op's HLO text: its op_name}} from the ``tf_op`` stat
    of each op's event metadata (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5, map entries key = 1, value = 2;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2)."""
    buf = memoryview(data)
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for n, v in _fields(buf, *plane):
            if n == 2:
                name = _text(buf, v)
            elif n == 4:
                metas.append(v)
            elif n == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry[2])) if 2 in entry else {}
                if 2 in meta:
                    stat_names[entry.get(1, 0)] = _text(buf, meta[2])
        if not tracecalc.is_device_plane(name):
            continue
        ids = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        ops = {}
        for entry_span in metas:
            entry = dict(_fields(buf, *entry_span))
            if 2 not in entry:
                continue
            op, tf_op = None, None
            for n, v in _fields(buf, *entry[2]):
                if n == 2:
                    op = _text(buf, v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in ids:
                        tf_op = _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7))
            if op is not None and tf_op:
                ops[op] = tf_op
        out[name] = ops
    return out


def in_window(recs: Iterable[Rec]) -> Optional[list[Rec]]:
    """The records that overlap the longest ``bench.window`` host span,
    clipped to it, the window first; None without one."""
    recs = list(recs)
    wins = [r for r in recs if r.name == tracecalc.WINDOW_SPAN
            and not tracecalc.is_device_plane(r.plane)]
    if not wins:
        return None
    win = max(wins, key=lambda r: r.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    out = [win]
    for r in recs:
        if r is win:
            continue
        a, b = max(r.start_ns, lo), min(r.end_ns, hi)
        if b > a or (r.dur_ns == 0 and lo <= r.start_ns < hi):
            out.append(dataclasses.replace(r, start_ns=a, dur_ns=b - a))
    return out


def window_records(path: Optional[str]) -> Optional[list[Rec]]:
    """``in_window(load(path))``; None without a trace or a window."""
    return in_window(load(path)) if path else None


def host_spans(recs: Iterable[Rec], names: Iterable[str]) -> list[Rec]:
    names = set(names)
    return [r for r in recs if r.name in names and not tracecalc.is_device_plane(r.plane)]


def is_program_span(r: Rec) -> bool:
    return r.name.startswith(PROGRAM_PREFIXES) and not tracecalc.is_device_plane(r.plane)


# ---------------------------------------------------------------------- #
# serving: the KV path's host time and bytes per decode step
# ---------------------------------------------------------------------- #
def decode_steps(recs: list[Rec]) -> int:
    return len(host_spans(recs, ["serve.decode"]))


def kv_host_ms(recs: Optional[list[Rec]]) -> Optional[float]:
    """Host time in ``kv.gather`` + ``kv.upload`` + ``kv.append`` +
    ``kv.write`` over the window's decode steps, in ms a step."""
    if not recs or not decode_steps(recs):
        return None
    total = sum(r.dur_ns for r in host_spans(recs, KV_HOST_SPANS))
    return total / 1e6 / decode_steps(recs)


def kv_h2d_mb(recs: Optional[list[Rec]]) -> Optional[float]:
    """``kv.upload``'s ``bytes_h2d`` over the window's decode steps, in
    10^6 bytes a step."""
    if not recs or not decode_steps(recs):
        return None
    total = sum(float(r.stat("bytes_h2d", 0)) for r in host_spans(recs, ["kv.upload"]))
    return total / 1e6 / decode_steps(recs)


# ---------------------------------------------------------------------- #
# runtime: programs compiled or loaded inside the window
# ---------------------------------------------------------------------- #
def compiles(recs: Optional[list[Rec]]) -> Optional[int]:
    """Programs built in the window, each compiled or loaded from the
    compile cache."""
    if recs is None:
        return None
    return len(host_spans(recs, [PROGRAM_BUILD_EVENT]))


# ---------------------------------------------------------------------- #
# staged programs: device op time by named scope
# ---------------------------------------------------------------------- #
def op_scope(r: Rec) -> Optional[str]:
    """The innermost ``sable.*`` scope in a device op's ``op_name``."""
    text = str(r.stat(OP_NAME_STAT, ""))
    at = {scope: text.rfind(scope) for scope in (PACK, MULTIPLY)}
    best = max(at, key=at.get)
    return best if at[best] >= 0 else None


def staged_ops(recs: list[Rec]) -> list[Rec]:
    """Device ops of the staged programs: every op on the ``XLA Ops`` line
    whose program (the ``XLA Modules`` event covering it on the same
    device) is not one of the benchmark's own ``jit_bench_*``."""
    bench: dict[str, list[tuple[float, float]]] = {}
    for r in recs:
        if tracecalc.is_device_plane(r.plane) and r.line == tracecalc.MODULE_LINE \
                and r.name.startswith("jit_bench_"):
            bench.setdefault(r.plane, []).append((r.start_ns, r.end_ns))
    out = []
    for r in recs:
        if not (tracecalc.is_device_plane(r.plane) and r.line == tracecalc.OP_LINE):
            continue
        mid = r.start_ns + r.dur_ns / 2
        if any(a <= mid <= b for a, b in bench.get(r.plane, ())):
            continue
        out.append(r)
    return out


def scope_time(recs: list[Rec]) -> dict:
    """Op time (ns) of the staged programs by innermost scope (None for
    ops under neither scope)."""
    out: dict = {}
    for r in staged_ops(recs):
        s = op_scope(r)
        out[s] = out.get(s, 0.0) + r.dur_ns
    return out


def pack_share(recs: Optional[list[Rec]]) -> Optional[float]:
    """Share of the staged programs' op time under ``sable.pack``, in %;
    None when no op carries a ``sable`` scope."""
    if not recs:
        return None
    t = scope_time(recs)
    total = sum(t.values())
    if total <= 0 or (PACK not in t and MULTIPLY not in t):
        return None
    return 100.0 * t.get(PACK, 0.0) / total


# ---------------------------------------------------------------------- #
# attribution: how much of a span or of the device's idle time the
# program's spans explain, by innermost span
# ---------------------------------------------------------------------- #
def innermost_split(intervals: Iterable[tuple[float, float]],
                    spans: list[Rec]) -> dict:
    """Time (ns) of ``intervals`` by the innermost (shortest) span of
    ``spans`` covering it; ``"none"`` where no span covers it."""
    out: dict = {}
    for a, b in intervals:
        over = [s for s in spans if s.start_ns < b and s.end_ns > a]
        cuts = sorted({a, b} | {t for s in over for t in (s.start_ns, s.end_ns) if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [s for s in over if s.start_ns <= x and s.end_ns >= y]
            name = min(cover, key=lambda s: s.dur_ns).name if cover else "none"
            out[name] = out.get(name, 0.0) + (y - x)
    return out


def idle_intervals(recs: list[Rec]) -> list[tuple[float, float]]:
    """The gaps between device ops on the busiest device, inside the
    window of ``in_window(...)`` records."""
    ops: dict[str, list[tuple[float, float]]] = {}
    for r in recs:
        if tracecalc.is_device_plane(r.plane) and r.line == tracecalc.OP_LINE:
            ops.setdefault(r.plane, []).append((r.start_ns, r.end_ns))
    if not ops:
        return []
    busy = {p: tracecalc._union(iv) for p, iv in ops.items()}
    first = max(busy, key=lambda p: sum(b - a for a, b in busy[p]))
    lo, hi = recs[0].start_ns, recs[0].end_ns  # the window (``in_window``)
    gaps, prev = [], lo
    for a, b in busy[first] + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def coverage(recs: Optional[list[Rec]]) -> Optional[dict]:
    """What the program's spans explain in the window: the share of the
    benchmark's ``scheduler.step`` host time under a program span and under
    each innermost one, and the same for the device's idle time.  None
    without program spans."""
    if not recs:
        return None
    prog = [r for r in recs if is_program_span(r)]
    if not prog:
        return None

    def shares(intervals):
        split = innermost_split(intervals, prog)
        total = sum(split.values())
        if total <= 0:
            return None
        by = {k: v / total for k, v in sorted(split.items(), key=lambda kv: -kv[1])}
        return {"seconds": total / 1e9, "covered": 1.0 - by.get("none", 0.0), "by_span": by}

    return {
        "scheduler.step": shares([(r.start_ns, r.end_ns)
                                  for r in host_spans(recs, ["scheduler.step"])]),
        "device_idle": shares(idle_intervals(recs)),
    }


def format_coverage(cov: dict) -> list[str]:
    """Log lines for ``coverage()``."""
    lines = []
    for what, s in cov.items():
        if s is None:
            lines.append(f"spans: {what}: nothing to attribute")
            continue
        parts = ", ".join(f"{k} {100 * v:.2f}%" for k, v in s["by_span"].items())
        lines.append(f"spans: {what} {s['seconds']:.6f} s, {100 * s['covered']:.2f}% under "
                     f"program spans; by innermost span: {parts}")
    return lines
