"""Mixture-of-Experts with top-k routing, capacity buckets and EP sharding.

Dispatch is the sort-free scatter formulation: each (token, k) assignment
gets a slot inside its expert's capacity bucket via a masked cumulative sum;
tokens beyond capacity are dropped (capacity_factor controls the trade).
The (E, C, d) buffers are what XLA SPMD reshards across the model axis
(expert parallelism) — the all-to-all shows up explicitly in the dry-run
HLO and is counted by the roofline.

Shared experts (DeepSeek-style) are a dense FFN branch added to the routed
output.  The router aux (load-balance) loss follows Switch/DeepSeek.

Expert parallelism by slice (``MoEConfig.held = (first, count)``): the
router keeps its E outputs and its top-k, but the layer holds only
``count`` experts' weights, ``(count, d, f)``, and computes for every
token its held experts' part of the result: each held expert's bucket has
a slot for every token (capacity = the token count), so no (token, held
expert) pair is ever dropped, at prefill or at decode.  What the absent
experts would add is left out; on one chip the layer runs without the
exchange that would bring it.

Named scopes (trace-time only): ``moe.route`` (router, softmax, top-k,
gates), ``moe.experts`` (dispatch to the experts, their FFN, the combine),
``moe.shared`` (the shared experts).  ``moe_apply_counted`` also returns
the (token, expert) pairs whose result reaches the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.ctx import DP, MODEL, constrain, fetch
from .config import ModelConfig
from .layers import _act, dense_init

__all__ = ["moe_init", "moe_apply", "moe_apply_counted"]


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    mc = cfg.moe
    d, f = cfg.d_model, mc.d_ff
    E = mc.n_held
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, mc.num_experts), scale=0.02, dtype=dtype),
        "w1": dense_init(ks[1], (E, d, f), dtype=dtype),
        "w2": dense_init(ks[2], (E, f, d), dtype=dtype),
    }
    if cfg.ffn_type == "swiglu":
        p["w3"] = dense_init(ks[3], (E, d, f), dtype=dtype)
    if mc.num_shared:
        sf = (mc.shared_d_ff or mc.d_ff) * mc.num_shared
        p["sw1"] = dense_init(ks[4], (d, sf), dtype=dtype)
        p["sw2"] = dense_init(ks[5], (sf, d), dtype=dtype)
        if cfg.ffn_type == "swiglu":
            p["sw3"] = dense_init(ks[6], (d, sf), dtype=dtype)
    return p


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    mc = cfg.moe
    if mc.dropless:
        # full capacity: an expert can receive at most one slot per token
        # (top_k indices are distinct), so C = tokens guarantees no drops;
        # round to the sparse block size so capacity blocks tile exactly
        bm = mc.dropless_block
        return max(bm, -(-tokens // bm) * bm)
    c = int(np.ceil(tokens * mc.top_k / mc.num_experts * mc.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def _dropless_ffn(p: dict, buf: jnp.ndarray, counts: jnp.ndarray,
                  tokens: int, cfg: ModelConfig) -> jnp.ndarray:
    """Expert FFN over only the OCCUPIED capacity blocks.

    The (G, E, C, d) buffer is viewed as one tall dense matrix of
    (dropless_block, d) row-blocks; each (group, expert) bucket occupies
    ``ceil(count/bm)`` of them.  With the per-expert weights stacked
    side-by-side as (d, E*f), the routed first matmul is exactly ``sdd``
    under the topology "bucket row-block x its expert's column-block"
    (inspection-free: the mask is derived in-trace from the routing
    counts), the activation runs elementwise on the block data, and
    ``dsd`` against the stacked (E*f, d) second weights maps back to the
    buffer.  Unvisited (empty) capacity blocks come back as zero rows, so
    the combine gather is unchanged.  FLOPs scale with occupied blocks
    (~ tokens * top_k), not with the dense E*C buffer.
    """
    from ..kernels.bsr_ops import dsd, sdd
    from ..sparse.block_csr import topology_from_mask

    mc = cfg.moe
    G, E, C, d = buf.shape
    f = p["w1"].shape[-1]
    bm = mc.dropless_block
    Cb = C // bm

    occ = -(-counts // bm)  # (G, E) blocks needed per bucket
    occ_mask = jnp.arange(Cb)[None, None, :] < occ[:, :, None]  # (G, E, Cb)
    eye = jnp.eye(E, dtype=bool)  # bucket (g, e) multiplies expert e only
    mask = (occ_mask[..., None] & eye[None, :, None, :]).reshape(G * E * Cb, E)
    # each expert wastes at most one partial block per group
    nnz_max = G * min(E * Cb, -(-tokens * mc.top_k // bm) + E)
    topo = topology_from_mask(mask, (bm, f), nnz_max=nnz_max)

    a = buf.reshape(G * E * C, d)
    w1 = fetch(p["w1"].astype(buf.dtype), None, None, None)
    h = sdd(a, jnp.transpose(w1, (1, 0, 2)).reshape(d, E * f), topo)
    if cfg.ffn_type == "swiglu":
        w3 = fetch(p["w3"].astype(buf.dtype), None, None, None)
        g = sdd(a, jnp.transpose(w3, (1, 0, 2)).reshape(d, E * f), topo)
        h = h.with_data(jax.nn.silu(h.data) * g.data)
    else:
        h = h.with_data(_act(cfg, h.data))
    w2 = fetch(p["w2"].astype(buf.dtype), None, None, None)
    out = dsd(h, w2.reshape(E * f, d))
    return out.reshape(G, E, C, d)


def _route(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """Router probabilities (..., E) in float32, and the top-k gates and
    expert ids (..., k); the gates renormalised with ``norm_topk_prob``."""
    mc = cfg.moe
    if mc.router_f32:
        logits = jnp.matmul(x.astype(jnp.float32),
                            fetch(p["router"].astype(jnp.float32), None, None),
                            precision=jax.lax.Precision.HIGHEST)
    else:
        logits = (x @ fetch(p["router"].astype(x.dtype), None, None)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, mc.top_k)
    if mc.norm_topk_prob:
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    return probs, gate, idx


def _aux_loss(probs, idx, cfg: ModelConfig):
    """Switch-style load-balance loss over the router's E experts."""
    E = cfg.moe.num_experts
    me = probs.reshape(-1, E).mean(axis=0)  # mean router prob per expert
    ce = jnp.bincount(idx.reshape(-1), length=E).astype(jnp.float32) / idx.size
    return cfg.moe.aux_loss_coef * E * jnp.sum(me * ce)


def _shared(p: dict, xt: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The shared experts: one dense FFN branch of width num_shared * d_ff."""
    h = xt @ fetch(p["sw1"].astype(xt.dtype), None, MODEL)
    if cfg.ffn_type == "swiglu":
        h = jax.nn.silu(h) * (xt @ fetch(p["sw3"].astype(xt.dtype), None, MODEL))
    else:
        h = _act(cfg, h)
    return h @ fetch(p["sw2"].astype(xt.dtype), MODEL, None)


def _held_experts(p: dict, xt: jnp.ndarray, gate, idx, cfg: ModelConfig):
    """The held experts' part of the result for tokens ``xt`` (T, d), and
    the (token, held expert) pairs it holds.  Every held expert takes every
    token (capacity = T), weighted by the token's gate for it, or 0 where
    the router did not pick it: dropless at any batch."""
    mc = cfg.moe
    local = idx - mc.held_first  # (T, k) ids within the slice
    here = (local >= 0) & (local < mc.n_held)
    pick = jax.nn.one_hot(jnp.where(here, local, -1), mc.n_held, dtype=jnp.float32)
    w = jnp.einsum("tk,tke->te", gate, pick)  # (T, held) combine weights
    h = jnp.einsum("td,edf->etf", xt, fetch(p["w1"].astype(xt.dtype), MODEL, None, None))
    if cfg.ffn_type == "swiglu":
        g = jnp.einsum("td,edf->etf", xt, fetch(p["w3"].astype(xt.dtype), MODEL, None, None))
        h = jax.nn.silu(h) * g
    else:
        h = _act(cfg, h)
    out = jnp.einsum("etf,efd->etd", h, fetch(p["w2"].astype(xt.dtype), MODEL, None, None))
    y = jnp.einsum("etd,te->td", out, w.astype(xt.dtype))
    return y, here.sum().astype(jnp.int32)


def moe_apply(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux_loss); see ``moe_apply_counted``."""
    y, aux, _ = moe_apply_counted(p, x, cfg)
    return y, aux


def moe_apply_counted(p: dict, x: jnp.ndarray, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux_loss, pairs): ``pairs`` (int32) counts the
    (token, expert) assignments whose expert output reaches ``y``.

    With a held slice (``MoEConfig.held``), the layer's own experts' part
    for every token (``_held_experts``); otherwise the capacity buckets:

    GShard-style grouped dispatch (§Perf iteration on deepseek-v2): tokens
    are bucketed per GROUP (= batch row, which is data-sharded), so the
    scatter into and gather out of the capacity buffer are LOCAL to each
    data shard — only the dense (G, E, Cg, d) buffer crosses the mesh (a
    clean all-to-all the partitioner handles), never gather/scatter
    semantics.  The global-buffer path had XLA lowering cross-shard
    scatters as replicate+all-reduce (~2 TB/device/step on deepseek-v2).

    Single-token decode (S == 1) keeps one global group: per-group
    capacity would pad E*Cg >> T there.
    """
    mc = cfg.moe
    B, S, d = x.shape
    E, k = mc.num_experts, mc.top_k
    if mc.held is not None:
        xt = x.reshape(B * S, d)
        with jax.named_scope("moe.route"):
            probs, gate, idx = _route(p, xt, cfg)
        with jax.named_scope("moe.experts"):
            y, pairs = _held_experts(p, xt, gate, idx, cfg)
        if mc.num_shared:
            with jax.named_scope("moe.shared"):
                y = y + _shared(p, xt, cfg)
        return y.reshape(B, S, d), _aux_loss(probs, idx, cfg), pairs

    if S > 1:
        G, Tg = B, S  # groups = batch rows (data-sharded)
    else:
        G, Tg = 1, B * S
    C = _capacity(Tg, cfg)
    xg = x.reshape(G, Tg, d)

    with jax.named_scope("moe.route"):
        probs, gate, idx = _route(p, xg, cfg)  # (G, Tg, E), (G, Tg, k)

    with jax.named_scope("moe.experts"):
        # slot within the (group, expert) bucket via per-group masked cumsum
        flat_idx = idx.reshape(G, Tg * k)
        onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)  # (G, Tg*k, E)
        slot_flat = jnp.cumsum(onehot, axis=1) - onehot
        slot = jnp.take_along_axis(slot_flat, flat_idx[..., None], axis=2)[..., 0]
        slot = slot.reshape(G, Tg, k)
        dropped = slot >= C
        pairs = (~dropped).sum().astype(jnp.int32)
        slot = jnp.where(dropped, C, slot)  # OOB => scatter mode='drop'

        # dispatch: local scatter into (G, E, Cg, d).  vmap over G keeps the
        # group dim a BATCH dim of the scatter, so the partitioner shards it
        # over dp instead of replicating (explicit 3-D index arrays defeat
        # batch-dim detection and cost ~80 TB/device — §Perf iteration log).
        xk = jnp.broadcast_to(xg[:, :, None, :], (G, Tg, k, d))
        buf = jax.vmap(
            lambda i, s, v: jnp.zeros((E, C, d), xg.dtype).at[i, s].set(
                v, mode="drop"
            )
        )(idx, slot, xk)
        buf = constrain(buf, DP, MODEL, None, None)

        if mc.dropless:
            # dropless: FFN only over occupied capacity blocks (block-sparse
            # sdd/dsd over an in-trace topology; single flattened matrix, so
            # no EP resharding — the dropless path is the per-batch-topology
            # regime, not the EP-sharded dense-buffer one)
            counts = onehot.sum(axis=1)  # (G, E) tokens routed per bucket
            out_buf = _dropless_ffn(p, buf, counts, Tg, cfg)
        else:
            # expert FFN: batched einsum; E sharded over 'model' (EP) — the
            # (G@dp, E, C, d) -> (G, E@model, C, d) reshard is the EP all-to-all
            h = jnp.einsum("gecd,edf->gecf", buf,
                           fetch(p["w1"].astype(xg.dtype), MODEL, None, None))
            if cfg.ffn_type == "swiglu":
                g = jnp.einsum("gecd,edf->gecf", buf,
                               fetch(p["w3"].astype(xg.dtype), MODEL, None, None))
                h = jax.nn.silu(h) * g
            else:
                h = _act(cfg, h)
            out_buf = jnp.einsum("gecf,efd->gecd", h,
                                 fetch(p["w2"].astype(xg.dtype), MODEL, None, None))
        # return expert outputs to the data shards BEFORE the combine gather:
        # an explicit all-gather over 'model' of the dense buffer (~0.3 GB per
        # group) so the gather below stays local — letting the partitioner
        # handle an E-sharded gather costs ~5x more (replicate+AR of (T,k,d))
        out_buf = constrain(out_buf, DP, None, None, None)

        # combine: local gather per group; dropped tokens contribute zero
        gathered = jax.vmap(
            lambda b, i, s: b.at[i, s].get(mode="fill", fill_value=0)
        )(out_buf, idx, slot)  # (G, Tg, k, d)
        gathered = constrain(gathered, DP, None, None, None)
        y = (gathered * gate[..., None].astype(xg.dtype)).sum(axis=2)
    y = y.reshape(B * S, d)
    xt = x.reshape(B * S, d)

    # shared experts (dense branch)
    if mc.num_shared:
        with jax.named_scope("moe.shared"):
            y = y + _shared(p, xt, cfg)

    return y.reshape(B, S, d), _aux_loss(probs, idx, cfg), pairs
