"""Attention mixers: GQA (+RoPE, optional qk-norm) and MLA (DeepSeek-V2).

Cache layouts (per layer):
  gqa   {"k": (B, S_max, Kv, Dh), "v": (B, S_max, Kv, Dh)}
  mla   {"ckv": (B, S_max, kv_lora), "kr": (B, S_max, rope_dim)}
  cross {"k": (B, S_src, Kv, Dh), "v": ...}  (computed once at prefill)

Decode uses the *absorbed* MLA formulation (score/value contractions in the
compressed kv_lora space) so per-step cost is O(S * (kv_lora + rope)) per
head — the memory/bandwidth saving that motivates MLA.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.ctx import MODEL, fetch
from .config import ModelConfig
from .layers import dense_init, rmsnorm, rope, yarn_mscale

__all__ = ["gqa_init", "gqa_apply", "mla_init", "mla_apply", "cross_init", "cross_apply"]

NEG_INF = -1e30


def _sdpa(q, k, v, mask) -> jnp.ndarray:
    """q: (B,Sq,K,G,Dh) grouped; k,v: (B,Sk,K,Dh); mask: (B,1,1,Sq,Sk) or None."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out


def _sdpa_chunked(q, k, v, q_offset, causal: bool, chunk: int) -> jnp.ndarray:
    """Streaming-softmax (flash) attention: scan over key chunks with
    running (m, l, acc) — never materializes (Sq, Sk) scores.  Numerically
    identical to `_sdpa` (same f32 softmax accumulation).

    q: (B,Sq,K,G,D) at global positions q_offset+i; k/v: (B,Sk,K,D).
    """
    B, Sq, K, G, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (Sk + pad) // chunk
    kc = k.reshape(B, nc, chunk, K, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, K, D).transpose(1, 0, 2, 3, 4)
    kpos = (jnp.arange(nc * chunk).reshape(nc, chunk))
    qpos = q_offset + jnp.arange(Sq)

    m0 = jnp.full((B, K, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, K, G, D), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kci, vci, kpi = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kci).astype(jnp.float32) * scale
        valid = (kpi < Sk)[None, :]
        if causal:
            valid = valid & (kpi[None, :] <= qpos[:, None])
        s = jnp.where(valid[None, None, None], s, -jnp.inf)
        m2 = jnp.maximum(m, s.max(axis=-1))
        # exp(-inf - -inf) guard: rows with no valid keys yet keep l=0
        p = jnp.exp(s - jnp.where(jnp.isinf(m2), 0.0, m2)[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - jnp.where(
            jnp.isinf(m2), 0.0, m2)))
        l2 = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), vci)
        acc2 = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m2, l2, acc2), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kpos))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype)


def causal_mask(sq: int, sk: int, offset) -> jnp.ndarray:
    """(1,1,1,Sq,Sk) boolean: query i (global pos offset+i) sees key j<=pos."""
    qpos = offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    return (kpos <= qpos)[None, None, None]


# ---------------------------------------------------------------------- #
# GQA
# ---------------------------------------------------------------------- #
def gqa_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, H, Kv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * Dh), dtype=dtype),
        "wk": dense_init(ks[1], (d, Kv * Dh), dtype=dtype),
        "wv": dense_init(ks[2], (d, Kv * Dh), dtype=dtype),
        "wo": dense_init(ks[3], (H * Dh, d), dtype=dtype),
    }
    if cfg.qk_norm:
        p["qn"] = jnp.ones((Dh,), dtype)
        p["kn"] = jnp.ones((Dh,), dtype)
    return p


def gqa_apply(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    positions: jnp.ndarray,  # (S,) or (B,S) global positions of x tokens
    cache: Optional[dict] = None,
    cache_pos=None,  # scalar write offset into cache (decode/prefill)
    causal: bool = True,
):
    B, S, d = x.shape
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // Kv
    q = (x @ fetch(p["wq"].astype(x.dtype), None, MODEL)).reshape(B, S, H, Dh)
    k = (x @ fetch(p["wk"].astype(x.dtype), None, MODEL)).reshape(B, S, Kv, Dh)
    v = (x @ fetch(p["wv"].astype(x.dtype), None, MODEL)).reshape(B, S, Kv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"], cfg.norm_eps)
        k = rmsnorm(k, p["kn"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

    new_cache = None
    if cache is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_pos, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_pos, 1)
        new_cache = {"k": ck, "v": cv}
        k_all, v_all = ck.astype(x.dtype), cv.astype(x.dtype)
        q_offset = cache_pos
    else:
        k_all, v_all = k, v
        q_offset = 0

    qg = q.reshape(B, S, Kv, G, Dh)
    chunk = cfg.attn_chunk
    if chunk and S > 1 and k_all.shape[1] >= 2 * chunk:
        # flash-style streaming softmax: no (Sq, Sk) materialization
        out = _sdpa_chunked(qg, k_all, v_all, q_offset, causal, chunk)
    else:
        sk = k_all.shape[1]
        mask = causal_mask(S, sk, q_offset) if causal else None
        out = _sdpa(qg, k_all, v_all, mask)
    out = out.reshape(B, S, H * Dh)
    return out @ fetch(p["wo"].astype(x.dtype), MODEL, None), new_cache


# ---------------------------------------------------------------------- #
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------- #
def mla_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    if m.q_lora_rank is None:  # queries projected directly (DeepSeek-V2-Lite)
        q = {"wq": dense_init(ks[0], (d, H * qk), dtype=dtype)}
    else:
        q = {
            "wdq": dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
            "qln": jnp.ones((m.q_lora_rank,), dtype),
            "wuq": dense_init(ks[1], (m.q_lora_rank, H * qk), dtype=dtype),
        }
    return {
        **q,
        "wdkv": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim), dtype=dtype),
        "kvln": jnp.ones((m.kv_lora_rank,), dtype),
        "wukv": dense_init(
            ks[3], (m.kv_lora_rank, H * (m.qk_nope_dim + m.v_head_dim)), dtype=dtype
        ),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), dtype=dtype),
    }


def mla_apply(
    p: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    cache: Optional[dict] = None,
    cache_pos=None,
    causal: bool = True,
    absorb: Optional[bool] = None,
):
    """MLA forward.  ``absorb=None`` auto: absorbed path for single-token
    decode, materialized path for train/prefill."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim
    if absorb is None:
        absorb = cache is not None and S == 1

    if m.q_lora_rank is None:
        q = x @ fetch(p["wq"].astype(x.dtype), None, MODEL)
    else:
        cq = rmsnorm(x @ fetch(p["wdq"].astype(x.dtype), None, None), p["qln"], cfg.norm_eps)
        q = cq @ fetch(p["wuq"].astype(x.dtype), None, MODEL)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    ckv_full = x @ fetch(p["wdkv"].astype(x.dtype), None, None)  # (B,S,kv_lora+dr)
    ckv = rmsnorm(ckv_full[..., : m.kv_lora_rank], p["kvln"], cfg.norm_eps)
    k_rope = rope(ckv_full[..., None, m.kv_lora_rank :], positions, cfg.rope_theta,
                  cfg.rope_scaling)[:, :, 0]  # (B,S,dr) shared across heads

    new_cache = None
    if cache is not None:
        cckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), cache_pos, 1
        )
        ckr = jax.lax.dynamic_update_slice_in_dim(
            cache["kr"], k_rope.astype(cache["kr"].dtype), cache_pos, 1
        )
        new_cache = {"ckv": cckv, "kr": ckr}
        ckv_all, kr_all = cckv.astype(x.dtype), ckr.astype(x.dtype)
        mask = causal_mask(S, ckv_all.shape[1], cache_pos)
    else:
        ckv_all, kr_all = ckv, k_rope
        mask = causal_mask(S, S, 0) if causal else None

    wukv = fetch(p["wukv"].astype(x.dtype), None, MODEL).reshape(m.kv_lora_rank, H, dn + dv)
    with jax.named_scope("mla.attend"):
        out = _mla_attend(q_nope, q_rope, ckv_all, kr_all, wukv, mask, mla_scale(cfg),
                          absorb)
    return out @ fetch(p["wo"].astype(x.dtype), MODEL, None), new_cache


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale ``(qk_nope + qk_rope)^-1/2``, times YaRN's
    ``mscale(factor, mscale_all_dim)^2`` where the rope is scaled."""
    m = cfg.mla
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_attend(q_nope, q_rope, ckv_all, kr_all, wukv, mask, scale, absorb):
    """The score and value contractions over the latent cache (the
    ``mla.attend`` scope), absorbed (decode) or with K and V materialised
    (train, prefill)."""
    B, S, H, dn = q_nope.shape
    sk, dr = ckv_all.shape[1], q_rope.shape[-1]
    dv = wukv.shape[-1] - dn
    wuk, wuv = wukv[..., :dn], wukv[..., dn:]  # (kv_lora, H, dn/dv)
    dt = q_nope.dtype
    if absorb:
        # score = (q_nope @ wuk^T) . ckv + q_rope . k_rope  — MQA-like in
        # compressed space; per-step cost O(S*(kv_lora+dr)) per head.
        q_c = jnp.einsum("bqhd,chd->bqhc", q_nope, wuk)  # (B,S,H,kv_lora)
        s1 = jnp.einsum("bqhc,bsc->bhqs", q_c, ckv_all)
        s2 = jnp.einsum("bqhd,bsd->bhqs", q_rope, kr_all)
        scores = (s1 + s2).astype(jnp.float32) * scale
        if mask is not None:
            scores = jnp.where(mask[:, 0], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        o_c = jnp.einsum("bhqs,bsc->bqhc", probs, ckv_all)  # compressed values
        return jnp.einsum("bqhc,chd->bqhd", o_c, wuv).reshape(B, S, H * dv)
    kv = jnp.einsum("bsc,chd->bshd", ckv_all, wukv)  # materialized k_nope|v
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None], (B, sk, H, dr))], axis=-1
    )
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    scores = jnp.einsum("bqhd,bshd->bhqs", qf, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, 0], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v).reshape(B, S, H * dv)


# ---------------------------------------------------------------------- #
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------- #
def cross_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, H, Kv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * Dh), dtype=dtype),
        "wk": dense_init(ks[1], (d, Kv * Dh), dtype=dtype),
        "wv": dense_init(ks[2], (d, Kv * Dh), dtype=dtype),
        "wo": dense_init(ks[3], (H * Dh, d), dtype=dtype),
    }


def cross_kv(p: dict, enc_out: jnp.ndarray, cfg: ModelConfig):
    B, Sk, _ = enc_out.shape
    Kv, Dh = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ fetch(p["wk"].astype(enc_out.dtype), None, MODEL)).reshape(B, Sk, Kv, Dh)
    v = (enc_out @ fetch(p["wv"].astype(enc_out.dtype), None, MODEL)).reshape(B, Sk, Kv, Dh)
    return {"k": k, "v": v}


def cross_apply(p: dict, x: jnp.ndarray, kv: dict, cfg: ModelConfig):
    B, S, d = x.shape
    H, Kv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // Kv
    q = (x @ fetch(p["wq"].astype(x.dtype), None, MODEL)).reshape(B, S, Kv, G, Dh)
    out = _sdpa(q, kv["k"].astype(x.dtype), kv["v"].astype(x.dtype), None)
    return out.reshape(B, S, H * Dh) @ fetch(p["wo"].astype(x.dtype), MODEL, None)
