"""Model assembly: groups of scanned blocks -> LM / enc-dec forward passes.

Public entry points (all pure functions of (params, cfg, inputs)):

  init_params(cfg, key)                         -> params pytree
  forward_train(params, cfg, batch)             -> (logits, aux_loss)
  init_cache(cfg, batch, s_max, dtype)          -> cache pytree
  prefill(params, cfg, tokens, cache)           -> (logits, cache)
  decode_step(params, cfg, token, cache, pos)   -> (logits, cache)
  (``counts=True`` on prefill, prefill_chunk and decode_step appends the
  int32 count of (token, expert) pairs the MoE layers computed)
  encode(params, cfg, src_embeds)               -> enc_out  (enc-dec only)

``lax.scan`` over stacked per-group parameters keeps HLO size independent
of depth; caches are stacked along the same axis and threaded through scan
as xs/ys.  Remat policy from cfg.remat wraps each block body.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.ctx import DP, MODEL, anchor_params, constrain, fetch
from .attention import (
    cross_apply,
    cross_init,
    cross_kv,
    gqa_apply,
    gqa_init,
    mla_apply,
    mla_init,
)
from .config import GroupSpec, LayerSpec, ModelConfig
from .layers import dense_init, ffn_apply, ffn_init, rmsnorm
from .moe import moe_apply_counted, moe_init
from .ssm import mamba_apply, mamba_decode, mamba_init

__all__ = [
    "init_params",
    "forward_train",
    "init_cache",
    "prefill",
    "decode_step",
    "encode",
]


def _cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------- #
# Init
# ---------------------------------------------------------------------- #
def _sublayer_init(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    ks = jax.random.split(key, 4)
    p = {}
    if spec.mixer == "gqa":
        p["mixer"] = gqa_init(ks[0], cfg, dtype)
        p["ln_mixer"] = jnp.ones((cfg.d_model,), dtype)
    elif spec.mixer == "mla":
        p["mixer"] = mla_init(ks[0], cfg, dtype)
        p["ln_mixer"] = jnp.ones((cfg.d_model,), dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_init(ks[0], cfg, dtype)
        p["ln_mixer"] = jnp.ones((cfg.d_model,), dtype)
    if spec.cross_attn:
        p["cross"] = cross_init(ks[1], cfg, dtype)
        p["ln_cross"] = jnp.ones((cfg.d_model,), dtype)
    if spec.ffn == "dense":
        p["ffn"] = ffn_init(ks[2], cfg, dtype=dtype)
        p["ln_ffn"] = jnp.ones((cfg.d_model,), dtype)
    elif spec.ffn == "moe":
        p["moe"] = moe_init(ks[3], cfg, dtype)
        p["ln_ffn"] = jnp.ones((cfg.d_model,), dtype)
    return p


def _block_init(key, cfg, specs, dtype):
    ks = jax.random.split(key, len(specs))
    return {f"sub{i}": _sublayer_init(ks[i], cfg, s, dtype) for i, s in enumerate(specs)}


def _group_init(key, cfg, g: GroupSpec, dtype):
    keys = jax.random.split(key, g.repeat)
    return jax.vmap(lambda k: _block_init(k, cfg, g.layers, dtype))(keys)


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = _pdtype(cfg)
    n_groups = len(cfg.groups) + len(cfg.enc_groups)
    ks = jax.random.split(key, n_groups + 3)
    params = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), 0.02, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "groups": [
            _group_init(ks[3 + i], cfg, g, dtype) for i, g in enumerate(cfg.groups)
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), 0.02, dtype)
    if cfg.is_encdec:
        off = 3 + len(cfg.groups)
        params["enc_groups"] = [
            _group_init(
                jax.random.fold_in(ks[2], i), cfg, g, dtype
            )
            for i, g in enumerate(cfg.enc_groups)
        ]
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dtype)
        if cfg.frontend_dim and cfg.frontend_dim != cfg.d_model:
            params["frontend_proj"] = dense_init(
                ks[2], (cfg.frontend_dim, cfg.d_model), dtype=dtype
            )
    return params


# ---------------------------------------------------------------------- #
# Block apply (one scan step)
# ---------------------------------------------------------------------- #
def _block_apply(
    cfg: ModelConfig,
    specs,
    p_slice: dict,
    x,
    positions,
    cache_slice: Optional[dict],
    cache_pos,
    causal: bool,
    enc_out,
    mode: str,
):
    new_cache = {}
    aux = jnp.zeros((), jnp.float32)
    pairs = jnp.zeros((), jnp.int32)
    eps = cfg.norm_eps
    for i, spec in enumerate(specs):
        p = p_slice[f"sub{i}"]
        c = cache_slice.get(f"sub{i}") if cache_slice is not None else None
        if spec.mixer in ("gqa", "mla"):
            h = rmsnorm(x, p["ln_mixer"], eps)
            fn = gqa_apply if spec.mixer == "gqa" else mla_apply
            o, nc = fn(
                p["mixer"],
                h,
                cfg,
                positions,
                cache=c.get("attn") if c else None,
                cache_pos=cache_pos,
                causal=causal,
            )
            x = x + o
            if c is not None:
                new_cache.setdefault(f"sub{i}", {})["attn"] = nc
        elif spec.mixer == "mamba":
            h = rmsnorm(x, p["ln_mixer"], eps)
            if mode == "decode":
                o, nc = mamba_decode(p["mixer"], h, cfg, c["ssm_cache"])
            else:
                o, nc = mamba_apply(
                    p["mixer"], h, cfg, return_cache=(c is not None)
                )
            x = x + o
            if c is not None:
                new_cache.setdefault(f"sub{i}", {})["ssm_cache"] = nc
        if spec.cross_attn:
            h = rmsnorm(x, p["ln_cross"], eps)
            if c is not None and "cross" in c and mode == "decode":
                kv = c["cross"]
            else:
                kv = cross_kv(p["cross"], enc_out, cfg)
            x = x + cross_apply(p["cross"], h, kv, cfg)
            if c is not None:
                new_cache.setdefault(f"sub{i}", {})["cross"] = kv
        if spec.ffn == "dense":
            x = x + ffn_apply(p["ffn"], rmsnorm(x, p["ln_ffn"], eps), cfg)
        elif spec.ffn == "moe":
            y, a, n = moe_apply_counted(p["moe"], rmsnorm(x, p["ln_ffn"], eps), cfg)
            x = x + y
            aux = aux + a
            pairs = pairs + n
        # keep the residual stream batch-sharded between sub-layers so the
        # SPMD partitioner never round-trips it through other layouts
        x = constrain(x, DP, None, None)
    return x, new_cache, aux, pairs


def _run_groups(
    cfg: ModelConfig,
    groups,
    group_params,
    x,
    positions,
    caches,
    cache_pos,
    causal,
    enc_out,
    mode: str,
):
    """Scan each group's stacked params (and cache) over its repeat dim.
    Returns (x, caches, aux loss, MoE pairs computed)."""
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    pairs_total = jnp.zeros((), jnp.int32)
    for gi, g in enumerate(groups):
        specs = g.layers
        gp = group_params[gi]
        gc = caches[gi] if caches is not None else None

        def body(carry, xs):
            x, aux, pairs = carry
            p_slice, c_slice = xs
            # pin the dynamic-sliced layer weights to their storage layout
            # before the TP-layout fetches (see ctx.anchor_params)
            p_slice = anchor_params(p_slice)
            out, nc, a, n = _block_apply(
                cfg, specs, p_slice, x, positions, c_slice, cache_pos,
                causal, enc_out, mode,
            )
            return (out, aux + a, pairs + n), nc

        if cfg.remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif cfg.remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                prevent_cse=False,
            )

        (x, aux_total, pairs_total), nc_stack = jax.lax.scan(
            body, (x, aux_total, pairs_total), (gp, gc)
        )
        new_caches.append(nc_stack)
    return x, new_caches, aux_total, pairs_total


# ---------------------------------------------------------------------- #
# Cache construction
# ---------------------------------------------------------------------- #
def _sub_cache(cfg: ModelConfig, spec: LayerSpec, batch, s_max, enc_len, dtype):
    c = {}
    if spec.mixer == "gqa":
        kv = (batch, s_max, cfg.n_kv_heads, cfg.head_dim)
        c["attn"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    elif spec.mixer == "mla":
        m = cfg.mla
        c["attn"] = {
            "ckv": jnp.zeros((batch, s_max, m.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, s_max, m.qk_rope_dim), dtype),
        }
    elif spec.mixer == "mamba":
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        ch = di + 2 * s.n_groups * s.d_state
        c["ssm_cache"] = {
            "conv": jnp.zeros((batch, s.d_conv - 1, ch), dtype),
            "ssm": jnp.zeros(
                (batch, s.n_heads(cfg.d_model), s.d_state, s.head_dim), dtype
            ),
        }
    if spec.cross_attn:
        kv = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
        c["cross"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    return c


def init_cache(cfg: ModelConfig, batch: int, s_max: int, enc_len: int = 0, dtype=None):
    """Decode-capacity cache, stacked (repeat, ...) per group."""
    dtype = dtype or _cdtype(cfg)

    def one_group(g: GroupSpec):
        block = {
            f"sub{i}": _sub_cache(cfg, s, batch, s_max, enc_len, dtype)
            for i, s in enumerate(g.layers)
            if _sub_cache(cfg, s, batch, s_max, enc_len, dtype)
        }
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.repeat,) + a.shape), block
        )

    return [one_group(g) for g in cfg.groups]


# ---------------------------------------------------------------------- #
# Entry points
# ---------------------------------------------------------------------- #
def _embed(params, cfg, tokens):
    x = fetch(params["embed"].astype(_cdtype(cfg)), MODEL, None)[tokens]
    return constrain(x, DP, None, None)


def _unembed(params, cfg, x):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ fetch(params["embed"].astype(x.dtype), MODEL, None).T
    else:
        logits = x @ fetch(params["lm_head"].astype(x.dtype), None, MODEL)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return constrain(logits, DP, None, MODEL)  # vocab-sharded logits


def encode(params, cfg: ModelConfig, src_embeds):
    """Encoder stack over precomputed frontend embeddings (B, S_src, D)."""
    x = src_embeds.astype(_cdtype(cfg))
    if "frontend_proj" in params:
        x = x @ params["frontend_proj"].astype(x.dtype)
    positions = jnp.arange(x.shape[1])
    x, _, _, _ = _run_groups(
        cfg, cfg.enc_groups, params["enc_groups"], x, positions,
        None, None, False, None, "train",
    )
    return rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def forward_train(params, cfg: ModelConfig, batch: dict):
    """Teacher-forced forward.  batch: {"tokens": (B,S)} and, for enc-dec,
    {"src_embeds": (B,S_src,frontend_dim)}.  Returns (logits, aux)."""
    tokens = batch["tokens"]
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["src_embeds"])
    x = _embed(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])
    x, _, aux, _ = _run_groups(
        cfg, cfg.groups, params["groups"], x, positions, None, None,
        cfg.causal, enc_out, "train",
    )
    return _unembed(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, tokens, cache, enc_out=None, counts=False):
    """Process the prompt, filling the cache at positions [0, S)."""
    x = _embed(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])
    x, new_cache, _, pairs = _run_groups(
        cfg, cfg.groups, params["groups"], x, positions, cache, 0,
        cfg.causal, enc_out, "prefill",
    )
    out = (_unembed(params, cfg, x[:, -1:]), new_cache)
    return out + (pairs,) if counts else out


def prefill_chunk(params, cfg: ModelConfig, tokens, cache, start, enc_out=None,
                  counts=False):
    """Process prompt positions [start, start+S), writing the cache at the
    same offsets and attending over every cached position <= each query
    (`cache_pos` drives both the write offset and the causal-mask offset in
    the attention layers).  With start == 0 this is exactly ``prefill``.

    Only valid for models whose cache is entirely attention KV: a Mamba/SSM
    sub-layer in "prefill" mode recomputes its state from scratch over just
    this chunk, so chunked callers (the serving scheduler) must gate on a
    fully-paged cache."""
    x = _embed(params, cfg, tokens)
    positions = start + jnp.arange(tokens.shape[1])
    x, new_cache, _, pairs = _run_groups(
        cfg, cfg.groups, params["groups"], x, positions, cache, start,
        cfg.causal, enc_out, "prefill",
    )
    out = (_unembed(params, cfg, x[:, -1:]), new_cache)
    return out + (pairs,) if counts else out


def decode_step(params, cfg: ModelConfig, token, cache, pos, enc_out=None, counts=False):
    """One decode step.  token: (B, 1) int32, pos: scalar int32 position."""
    x = _embed(params, cfg, token)
    positions = jnp.full((token.shape[0], 1), pos, dtype=jnp.int32)
    x, new_cache, _, pairs = _run_groups(
        cfg, cfg.groups, params["groups"], x, positions, cache, pos,
        cfg.causal, enc_out, "decode",
    )
    out = (_unembed(params, cfg, x), new_cache)
    return out + (pairs,) if counts else out
