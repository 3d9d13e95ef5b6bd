"""Device op time of the serving programs by the program's named scopes.

The serving programs are the decode lane step (``jit(serve_lane_step)``)
and the prefills (``jit(serve_prefill)``, ``jit(serve_prefill_chunk)``),
told apart by the head of each device op's ``op_name`` (the ``tf_op`` stat
that ``spancalc`` attaches).  A scope (``moe.experts``, ``mla.attend``,
...) is one component of that path.  Only leaf ops count: an op whose
interval holds the next op on the same device (a layer scan's ``while``)
is left out, so that no time counts twice.  Nothing here imports the
program under test.
"""
from __future__ import annotations

from typing import Iterable, Optional

import spancalc
import tracecalc

PROGRAMS = {"jit(serve_lane_step)": "decode", "jit(serve_prefill": "prefill"}


def leaf_ops(recs: Iterable[spancalc.Rec]) -> list:
    """Device ops on the ``XLA Ops`` line that hold no other op."""
    by_plane: dict = {}
    for r in recs:
        if tracecalc.is_device_plane(r.plane) and r.line == tracecalc.OP_LINE:
            by_plane.setdefault(r.plane, []).append(r)
    out = []
    for ops in by_plane.values():
        ops.sort(key=lambda r: (r.start_ns, -r.dur_ns))
        for a, b in zip(ops, ops[1:] + [None]):
            if b is None or b.start_ns >= a.end_ns:
                out.append(a)
    return out


def program_of(r: spancalc.Rec) -> Optional[str]:
    text = str(r.stat(spancalc.OP_NAME_STAT, ""))
    for head, kind in PROGRAMS.items():
        if text.startswith(head):
            return kind
    return None


def scope_times(recs: Optional[list], scopes: Iterable[str]) -> Optional[dict]:
    """{(program, scope or None): op time in ns} over the serving programs'
    leaf ops; None without a trace or without a serving op."""
    if not recs:
        return None
    scopes = tuple(scopes)
    out: dict = {}
    for r in leaf_ops(recs):
        prog = program_of(r)
        if prog is None:
            continue
        parts = str(r.stat(spancalc.OP_NAME_STAT, "")).split("/")
        scope = next((s for s in scopes if s in parts), None)
        out[(prog, scope)] = out.get((prog, scope), 0.0) + r.dur_ns
    return out or None


def share(times: Optional[dict]) -> Optional[float]:
    """The scoped share of the serving programs' op time, in %; None when
    no op carries one of the scopes."""
    if not times:
        return None
    scoped = sum(v for (_, s), v in times.items() if s is not None)
    total = sum(times.values())
    return 100.0 * scoped / total if scoped > 0 and total > 0 else None


def format_times(times: dict) -> str:
    return ", ".join(f"{prog} {scope or 'other'} {ns / 1e9:.6f}"
                     for (prog, scope), ns in sorted(times.items(), key=lambda kv: -kv[1]))
