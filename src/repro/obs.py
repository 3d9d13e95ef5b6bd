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
                        positions and the argmax that waits for it;
                        moe_pairs (models with MoE layers): the (token,
                        expert) pairs its MoE layers computed
        kv.write        rid, bytes_d2h: the prefill cache scattered into the
                        request's pages on the device; bytes_d2h counts the
                        state leaves read back (0 for a fully-paged cache)
      serve.prefill     (chunked prefill: one chunk per mid-prefill lane,
      kv.upload          directly under serve.step; kv.upload's bytes_h2d
      kv.write           counts host leaves of the chunk's cache, none when
                         the cache is fully paged: it is gathered on the
                         device)
      serve.decode      lanes; moe_pairs (models with MoE layers): the
                        (token, expert) pairs the lanes' MoE layers
                        computed this step, read back in serve.readback
        kv.gather       bytes: each lane's write resolved on the host
                        (growth, copy-on-write, eviction under page
                        pressure) and the int32 page table built; bytes is
                        the table's
        kv.upload       bytes_h2d: the page table, tokens, positions, keys,
                        temperatures, write pages, queued page copies and
                        state leaves put on the device, waited for
        serve.lane_step the jitted lane step (gathers the views from the
                        device arenas, decodes, writes the new KV into the
                        pages) and the wait for its outputs
        serve.readback  bytes_d2h: next tokens, logits and state leaves
                        read back; kv_bytes: the state leaves among them
        kv.append       lanes: the host bookkeeping after the step
                        (``seq_len``, state leaves, tokens, finishes)

Every span of one request carries its ``rid``.  The same byte counts go
into the scheduler's ``stats`` (``kv_h2d_bytes``: step arguments and KV put
on the device; ``kv_d2h_bytes``: KV read back; ``decode_steps_with_view``:
decode steps dispatched; ``moe_pairs_here``: the ``moe_pairs`` above,
summed over the MoE layers of every prefill and decode step), so they are
seen without a profiler too.  The ``moe_pairs`` count comes back from the
jitted prefill and lane step beside their outputs and is read with them:
a span takes no stats once it has closed, so the decode step's count sits
on ``serve.decode``, the one open when the readback ends.
``kv_parked_bytes`` counts the KV bytes copied by eviction, resume and the
release of a parked sequence's shared pages — the only KV that still
crosses, and only under page pressure; it is part of ``kv_d2h_bytes``
(evict, release) and ``kv_h2d_bytes`` (resume) as well.

The staged sparse programs name their phases with ``jax.named_scope``
instead (trace-time only, free at run time): ``sable.pack`` around the
gathers that lay the runtime values out in blocks or tiles, and
``sable.multiply`` around the products and the scatter-adds.  The model
does the same inside the serving programs (``serve_prefill``,
``serve_prefill_chunk``, ``serve_lane_step``): ``moe.route`` (router,
softmax, top-k, gates), ``moe.experts`` (dispatch to the held experts,
their FFN, the combine), ``moe.shared`` (the shared experts) and
``mla.attend`` (latent attention's score and value contractions, absorbed
or materialised).  A scope reaches each device op through its ``op_name``
metadata.
"""
from __future__ import annotations

import jax

__all__ = ["span"]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` whose ``args`` become the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)
