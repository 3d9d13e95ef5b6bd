"""Spans of the serving step, named on the profiler's own clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, it records a host event with ``args`` as the event's
stats (numbers and strings) in the same ``.xplane.pb`` as the device's
operations, so program spans and device ops share one clock.  With no
trace running it costs under a microsecond.  Nothing here buffers or
exports: the profiler holds the events and writes them when the trace
stops.  ``set_metadata(**args)`` on the entered span adds stats known only
at its end.

Every span the program records, indented under its parent, with its
arguments:

    serve.step          running (lanes decoding), admitted (requests admitted)
      serve.admit
        serve.prefill   rid, tokens: the jitted prefill of ``tokens`` prompt
                        positions and the argmax that waits for it
        kv.write        rid, bytes_d2h: the prefill cache read back and
                        copied into the request's pages
      serve.prefill     (chunked prefill: one chunk per mid-prefill lane,
      kv.write           directly under serve.step)
      serve.decode      lanes
        kv.gather       bytes: the dense batch view built on the host
        kv.upload       bytes_h2d: the view put on the device, waited for
        serve.lane_step the jitted lane step's dispatch and its outputs' wait
        serve.readback  bytes_d2h: next tokens, logits and KV slices read
                        back; kv_bytes: the KV slices among them
        kv.append       lanes: the per-lane appends into the pages

Every span of one request carries its ``rid``.  The same byte counts go
into the scheduler's ``stats`` (``kv_h2d_bytes``: views uploaded;
``kv_d2h_bytes``: KV slices and prefill caches read back;
``decode_steps_with_view``), so they are seen without a profiler too.

The staged sparse programs name their phases with ``jax.named_scope``
instead (trace-time only, free at run time): ``sable.pack`` around the
gathers that lay the runtime values out in blocks or tiles, and
``sable.multiply`` around the products and the scatter-adds.  The scope
reaches each device op through its ``op_name`` metadata.
"""
from __future__ import annotations

import jax

__all__ = ["span"]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` whose ``args`` become the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)
