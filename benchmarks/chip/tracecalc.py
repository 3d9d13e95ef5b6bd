"""Profiler capture, and the reduction from a trace to device metrics.

A traced run records one window with ``jax.profiler`` (Python tracing off,
so the host pays only for the runtime's own trace points and the
benchmark's spans).  The reduction reads the ``.xplane.pb`` file into
plain :class:`Event` records and computes, on the trace's own clock:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
* ``window_s``: the length of the benchmark's ``bench.window`` span;
* ``device_ops``: device time per operation name, longest first;
* ``modules``: device time and executions per XLA program (module);
* ``idle_gaps``: the gaps between busy intervals, each named by the
  innermost benchmark span (``staged.call``, ``scheduler.step``, ...) that
  covers it on the host.

Nothing here imports the program under test.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Iterable, Optional

WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith(DEVICE_PLANE_PREFIX) and not name.startswith("/device:CPU")


# ---------------------------------------------------------------------- #
# capture
# ---------------------------------------------------------------------- #
class Tracer:
    """Records the window that ``window()`` wraps; the profiler starts
    before the window opens and stops after it closes, so neither counts in
    it.  With ``enabled=False`` it records nothing."""

    def __init__(self, directory: str, enabled: bool):
        self.directory = directory
        self.enabled = enabled
        self.path: Optional[str] = None

    @contextlib.contextmanager
    def window(self):
        if not self.enabled:
            yield self
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield self
        finally:
            jax.profiler.stop_trace()
            found = sorted(glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                                     recursive=True))
            self.path = found[-1] if found else None


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace; next to free when none runs."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# ---------------------------------------------------------------------- #
# reading
# ---------------------------------------------------------------------- #
_HLO = re.compile(r"^%?([^ ]+) = ([a-z0-9]+\[[0-9,]*\])?.*?\b([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def short_name(name: str) -> str:
    """A device op's HLO text cut to its name, shape and op, e.g.
    ``fusion.536 f32[377478] fusion:kCustom``; other names pass through."""
    m = _HLO.match(name)
    if not m:
        return name
    kind = _KIND.search(name)
    op = m.group(3) + (":" + kind.group(1) if kind else "")
    return " ".join(x for x in (m.group(1), m.group(2), op) if x)


def load_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for e in line.events:
                name = short_name(e.name) if device else e.name
                out.append(Event(plane.name, line.name, name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(e: Event, lo: float, hi: float) -> Optional[tuple[float, float]]:
    a, b = max(e.start_ns, lo), min(e.end_ns, hi)
    return (a, b) if b > a else None


def reduce(events: list[Event], span_names: Iterable[str]) -> Optional[dict]:
    """Device metrics of the window in ``events``; None when the trace has
    no window span or no device operation inside it."""
    windows = [e for e in events if e.name == WINDOW_SPAN and not is_device_plane(e.plane)]
    if not windows:
        return None
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns
    window_ns = hi - lo

    ops_by_plane: dict[str, list[tuple[float, float]]] = {}
    op_time: dict[str, float] = {}
    modules: dict[str, list[float]] = {}
    for e in events:
        if not is_device_plane(e.plane):
            continue
        iv = _clip(e, lo, hi)
        if iv is None:
            continue
        if e.line == OP_LINE:
            ops_by_plane.setdefault(e.plane, []).append(iv)
            op_time[e.name] = op_time.get(e.name, 0.0) + (iv[1] - iv[0])
        elif e.line == MODULE_LINE:
            m = modules.setdefault(e.name, [0.0, 0])
            m[0] += iv[1] - iv[0]
            m[1] += 1
    if not ops_by_plane:
        return None

    busy = {p: _union(iv) for p, iv in ops_by_plane.items()}
    busy_ns = {p: sum(b - a for a, b in u) for p, u in busy.items()}
    busy_s = sum(busy_ns.values()) / len(busy_ns) / 1e9

    # gaps on the busiest device, named by the innermost benchmark span
    names = set(span_names)
    host = [e for e in events
            if not is_device_plane(e.plane) and e.name in names and e.name != WINDOW_SPAN]
    first = max(busy, key=lambda p: busy_ns[p])
    gaps, prev = [], lo
    for a, b in busy[first] + [(hi, hi)]:
        if a > prev:
            mid = (prev + a) / 2
            covering = [h for h in host if h.start_ns <= mid <= h.end_ns]
            label = min(covering, key=lambda h: h.dur_ns).name if covering else "none"
            gaps.append((label, (a - prev) / 1e9))
        prev = max(prev, b)
    gaps.sort(key=lambda g: -g[1])

    spans: dict[str, int] = {}
    for h in host:
        if h.start_ns >= lo and h.end_ns <= hi:
            spans[h.name] = spans.get(h.name, 0) + 1

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "devices": len(busy),
        "device_ops": sorted(((k, v / 1e9) for k, v in op_time.items()),
                             key=lambda kv: -kv[1]),
        "modules": {k: {"seconds": v[0] / 1e9, "count": int(v[1])}
                    for k, v in modules.items()},
        "idle_gaps": gaps,
        "spans": spans,
    }


def breakdown(red: dict) -> dict:
    """The ``breakdown`` entry of a traced run's result line."""
    return {
        "device_ops": [[k, v] for k, v in red["device_ops"][:TOP]],
        "idle_gaps": [[k, v] for k, v in red["idle_gaps"][:TOP]],
    }


def summarize_planes(events: list[Event], per_line: int = 3) -> list[str]:
    """Plane/line names with event counts and a few event names: printed
    by traced runs so that a reader can see what the trace holds."""
    seen: dict[tuple[str, str], list[Event]] = {}
    for e in events:
        seen.setdefault((e.plane, e.line), []).append(e)
    out = []
    for (plane, line), evs in sorted(seen.items()):
        sample = ", ".join(sorted({e.name for e in evs})[:per_line])
        out.append(f"{plane} | {line} | {len(evs)} events | {sample}")
    return out


def idle_percent(red: Optional[dict]) -> Optional[float]:
    """1 - busy / window, in %; None without a reduced trace."""
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
