"""Shared layer primitives: norms, RoPE, FFNs (dense + SABLE-sparse)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.ctx import MODEL, fetch
from ..sparse.linear import random_pattern, sparse_matmul_auto
from .config import ModelConfig, YarnRope

__all__ = [
    "rmsnorm",
    "rope",
    "yarn_inv_freq",
    "yarn_mscale",
    "ffn_apply",
    "ffn_init",
    "dense_init",
    "sable_patterns",
]


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1 * mscale * ln(scale) + 1`` (1 for
    ``scale <= 1``)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: YarnRope) -> tuple:
    """(inverse frequencies (dim/2,) float32, low, high) of YaRN rope, as
    DeepSeek-V2 computes them: frequencies above the correction range
    ``[low, high]`` are kept, those below are divided by ``yarn.factor``,
    and a linear ramp blends the two inside it."""
    def corr(rotations):
        return dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    base = 1.0 / theta ** (2 * i / dim)
    inv = base * (ramp / yarn.factor + (1.0 - ramp))
    return inv.astype(np.float32), low, high


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         scaling: Optional[YarnRope] = None) -> jnp.ndarray:
    """Rotary embeddings on the half-split layout.  x: (B, S, H, D),
    positions: (B, S) or (S,).  ``scaling`` takes the
    frequencies and the cos/sin magnitude from YaRN; the tables are
    computed once, at trace time."""
    d = x.shape[-1]
    half = d // 2
    if scaling is None:
        freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    else:
        freqs, _, _ = yarn_inv_freq(d, theta, scaling)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- #
# FFN
# ---------------------------------------------------------------------- #
def dense_init(key, shape, scale=None, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def sable_patterns(cfg: ModelConfig) -> dict:
    """Static block patterns for the sparsified FFN matrices (shared across
    layers — one staged executable pattern serves the whole stack)."""
    sb = cfg.sable
    pat_in = random_pattern(
        cfg.d_model, cfg.d_ff, sb.block_m, sb.block_n, sb.density, seed=sb.seed
    )
    pat_out = random_pattern(
        cfg.d_ff, cfg.d_model, sb.block_n, sb.block_m, sb.density, seed=sb.seed + 1
    )
    return {"in": pat_in, "out": pat_out}


def ffn_init(key, cfg: ModelConfig, d_ff: int = None, dtype=jnp.float32) -> dict:
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    if cfg.sable is not None and cfg.sable.target == "ffn":
        pats = sable_patterns(cfg)
        p_in, p_out = pats["in"], pats["out"]
        out = {
            "w1": dense_init(ks[0], (p_in.n_tiles, p_in.tm, p_in.tk), 1 / np.sqrt(d), dtype),
            "w2": dense_init(ks[1], (p_out.n_tiles, p_out.tm, p_out.tk), 1 / np.sqrt(d_ff), dtype),
        }
        if cfg.ffn_type == "swiglu":
            out["w3"] = dense_init(
                ks[2], (p_in.n_tiles, p_in.tm, p_in.tk), 1 / np.sqrt(d), dtype
            )
        return out
    out = {
        "w1": dense_init(ks[0], (d, d_ff), dtype=dtype),
        "w2": dense_init(ks[1], (d_ff, d), dtype=dtype),
    }
    if cfg.ffn_type == "swiglu":
        out["w3"] = dense_init(ks[2], (d, d_ff), dtype=dtype)
    return out


def _act(cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    if cfg.ffn_type == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(h)
        return r * r
    if cfg.ffn_type == "gelu":
        return jax.nn.gelu(h)
    return jax.nn.silu(h)


def ffn_apply(p: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dense or SABLE block-sparse FFN (swiglu / relu^2 / gelu)."""
    if cfg.sable is not None and p["w1"].ndim == 3:
        pats = sable_patterns(cfg)
        p_in, p_out = pats["in"], pats["out"]
        # out_model: the d_ff intermediate is the tensor-parallel dim — the
        # constraint resolves through the activation_sharding ctx (no-op
        # outside), matching the MODEL-sharded tiles fetched below
        h = sparse_matmul_auto(
            x, fetch(p["w1"].astype(x.dtype), MODEL), p_in, out_model=True
        )
        if cfg.ffn_type == "swiglu":
            g = sparse_matmul_auto(
                x, fetch(p["w3"].astype(x.dtype), MODEL), p_in, out_model=True
            )
            h = jax.nn.silu(h) * g
        else:
            h = _act(cfg, h)
        return sparse_matmul_auto(h, fetch(p["w2"].astype(x.dtype), MODEL), p_out)
    h = x @ fetch(p["w1"].astype(x.dtype), None, MODEL)
    if cfg.ffn_type == "swiglu":
        h = jax.nn.silu(h) * (x @ fetch(p["w3"].astype(x.dtype), None, MODEL))
    else:
        h = _act(cfg, h)
    return h @ fetch(p["w2"].astype(x.dtype), MODEL, None)
