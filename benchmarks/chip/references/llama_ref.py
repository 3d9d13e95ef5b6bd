"""Plain float32 reference for a decoder-only Llama-architecture model whose
SwiGLU FFN matrices are full, or SABLE block-sparse where the configuration
file has a ``sable`` entry.

The forward pass, the weights and the work count import nothing of the
program; ``program_config`` alone reaches it, to build the program's
configuration and check it against the file.  The mathematics follows the Llama
description that Granite Code uses (arXiv:2405.04324; HF ``LlamaForCausalLM``):
token embedding, then per layer ``x += Wo attn(rope(Wq n(x)), rope(Wk n(x)),
Wv n(x))`` with causal grouped-query attention (query head ``h`` reads
key/value head ``h // (H / Kv)``, scale ``1/sqrt(head_dim)``), rotary
embedding on the half-split layout, RMSNorm with a weight, and ``x +=
W2 (silu(W1 n(x)) * (W3 n(x)))``; a final RMSNorm and logits against the
tied embedding.  With ``sable``, each FFN matrix keeps only the tiles of its
block pattern; the pattern comes from a copy of the generator the
configuration names (``random_pattern`` below), and a tile ``(r, c)``
holds rows ``r*tm:(r+1)*tm`` and columns ``c*tk:(c+1)*tk`` of the
``(d_in, d_out)`` matrix, so ``y = x @ W``.

The weights are made here, from the seed, for the leaves of the program's
parameter tree (whose structure and shapes the caller passes in); the
reference reads the same leaves.  ``scores`` runs the model layer by layer
at float32 and ``highest`` matmul precision, so that it fits beside the
weights; with ``control=True`` the operands of every weight matmul are rounded
first to float8 (e4m3, one scale per tensor): the lower precision of the
control runs.

It keeps the serving driver's reference contract (``harness.py``):
``program_config``, ``make_params``, ``scores`` and ``work``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

import workcount

FP8_MAX = 448.0


# ---------------------------------------------------------------------- #
# structure: the block patterns (a copy of the configuration's generator)
# ---------------------------------------------------------------------- #
def random_pattern(d_in: int, d_out: int, tm: int, tk: int, density: float, seed: int):
    """Tile coordinates (rows, cols): a coverage diagonal, then cells from a
    seeded shuffle until ``round(density * R * C)`` tiles, sorted."""
    R, C = d_in // tm, d_out // tk
    rng = np.random.default_rng(seed)
    n = max(int(round(density * R * C)), max(R, C))
    chosen = {(i % R, i % C) for i in range(max(R, C))}
    cells = [(r, c) for r in range(R) for c in range(C)]
    rng.shuffle(cells)
    for cell in cells:
        if len(chosen) >= n:
            break
        chosen.add(cell)
    cells = sorted(chosen)
    return (np.asarray([r for r, _ in cells], np.int32),
            np.asarray([c for _, c in cells], np.int32))


def patterns(cfg: dict) -> dict:
    sb = cfg["sable"]
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    bm, bn = int(sb["block_m"]), int(sb["block_n"])
    return {
        "in": random_pattern(d, f, bm, bn, float(sb["density"]), int(sb["seed"])),
        "out": random_pattern(f, d, bn, bm, float(sb["density"]), int(sb["seed"]) + 1),
    }


# ---------------------------------------------------------------------- #
# the program's configuration, and the work a token needs
# ---------------------------------------------------------------------- #
def program_config(cfg: dict):
    """The program's ModelConfig for this configuration file, built from the
    file's ``program`` entry (``module:function`` plus ``replace``), with the
    file's ``sable`` or none, and checked against the file's published sizes
    and, with ``sable``, against this reference's tile patterns.  Raises on
    any departure."""
    from repro.models.config import SableConfig

    prog = cfg["program"]
    mod_name, fn_name = prog["config"].split(":")
    base = getattr(importlib.import_module(mod_name), fn_name)()
    sable = SableConfig(**cfg["sable"]) if "sable" in cfg else None
    mcfg = dataclasses.replace(base, sable=sable, **prog["replace"])
    want = {
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "n_layers": cfg["num_hidden_layers"], "rope_theta": cfg["rope_theta"],
        "norm_eps": cfg["rms_norm_eps"], "tie_embeddings": cfg["tie_word_embeddings"],
        "ffn_type": "swiglu", "attn_type": "gqa", "param_dtype": cfg["precision"]["weights"],
        "compute_dtype": cfg["precision"]["compute"],
    }
    got = {k: getattr(mcfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    specs = {(s.mixer, s.ffn, s.cross_attn) for g in mcfg.groups for s in g.layers}
    if bad or specs != {("gqa", "dense", False)} or mcfg.enc_groups:
        raise ValueError(f"program config departs from {cfg['name']}: {bad}, layers {specs}")
    if sable is not None:
        from repro.models.layers import sable_patterns

        mine, theirs = patterns(cfg), sable_patterns(mcfg)
        for name in ("in", "out"):
            p = theirs[name]
            if not (np.array_equal(p.rows, mine[name][0])
                    and np.array_equal(p.cols, mine[name][1])):
                raise ValueError(f"the program's {name} pattern is not the configuration's")
    return mcfg


def work(cfg: dict):
    """``(per_token, per_key, per_logits_row)`` operations that ``mfu.serve``
    counts (``workcount.decoder_flops``): the FFN's stored tiles with
    ``sable``, its three full matrices without."""
    if "sable" in cfg:
        pats, sb = patterns(cfg), cfg["sable"]
        tile = int(sb["block_m"]) * int(sb["block_n"])
        ffn = (2 * len(pats["in"][0]) + len(pats["out"][0])) * tile  # w1, w3; w2
    else:
        ffn = 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])
    return workcount.decoder_flops(cfg, ffn)


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf_law(name: str, cfg: dict):
    """(mean, std) of one leaf, by its place in the model."""
    d = int(cfg["hidden_size"])
    f = int(cfg["intermediate_size"])
    dens = float(cfg["sable"]["density"]) if "sable" in cfg else 1.0
    hd = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
    last = name.rsplit("/", 1)[-1]
    laws = {
        "embed": (0.0, 0.02),
        "final_norm": (1.0, 0.1),
        "ln_mixer": (1.0, 0.1),
        "ln_ffn": (1.0, 0.1),
        "wq": (0.0, d ** -0.5),
        "wk": (0.0, d ** -0.5),
        "wv": (0.0, d ** -0.5),
        "wo": (0.0, hd ** -0.5),
        "w1": (0.0, (dens * d) ** -0.5),
        "w3": (0.0, (dens * d) ** -0.5),
        "w2": (0.0, (dens * f) ** -0.5),
    }
    if last not in laws:
        raise KeyError(f"the reference does not know parameter {name!r}")
    return laws[last]


def make_params(cfg: dict, shapes, key):
    """Every leaf of ``shapes`` (a pytree of ShapeDtypeStructs) drawn from
    its law, in its dtype, on the device, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = []
    for path, s in flat:
        mean, std = _leaf_law(_leaf_name(path), cfg)
        specs.append((tuple(s.shape), jnp.dtype(s.dtype), mean, std))

    @jax.jit
    def draw(k):
        leaves = [
            (mean + std * jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
             ).astype(dtype)
            for i, (shape, dtype, mean, std) in enumerate(specs)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return draw(key)


# ---------------------------------------------------------------------- #
# the forward pass
# ---------------------------------------------------------------------- #
def _q8(a):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, control: bool):
    if control:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, theta):
    """Half-split rotary embedding over positions 0..S-1; x: (B, S, H, D)."""
    d = x.shape[-1]
    half = d // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dense(tiles, rows, cols, d_in, d_out):
    """The (d_in, d_out) float32 matrix whose only nonzero tiles are ``tiles``."""
    nt, tm, tk = tiles.shape
    grid = jnp.zeros((d_in // tm, d_out // tk, tm, tk), jnp.float32)
    grid = grid.at[rows, cols].set(tiles.astype(jnp.float32))
    return grid.transpose(0, 2, 1, 3).reshape(d_in, d_out)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(x, p, pin, pout, cfg_items, control):
    cfg = dict(cfg_items)
    B, S, d = x.shape
    H, Kv, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f, eps, theta = cfg["intermediate_size"], cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    h = _rmsnorm(x, f32(p["ln_mixer"]), eps)
    q = _rope(_mm(h, f32(p["mixer"]["wq"]), control).reshape(B, S, H, Dh), theta)
    k = _rope(_mm(h, f32(p["mixer"]["wk"]), control).reshape(B, S, Kv, Dh), theta)
    v = _mm(h, f32(p["mixer"]["wv"]), control).reshape(B, S, Kv, Dh)
    k = jnp.repeat(k, H // Kv, axis=2)  # query head h reads kv head h // (H/Kv)
    v = jnp.repeat(v, H // Kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST) / np.sqrt(Dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, S, H * Dh)
    x = x + _mm(o, f32(p["mixer"]["wo"]), control)

    h = _rmsnorm(x, f32(p["ln_ffn"]), eps)
    if pin is None:  # no sable: the full matrices
        w1, w3, w2 = (f32(p["ffn"][k]) for k in ("w1", "w3", "w2"))
    else:
        w1 = _dense(p["ffn"]["w1"], *pin, d, f)
        w3 = _dense(p["ffn"]["w3"], *pin, d, f)
        w2 = _dense(p["ffn"]["w2"], *pout, f, d)
    g = jax.nn.silu(_mm(h, w1, control)) * _mm(h, w3, control)
    return x + _mm(g, w2, control)


@jax.jit
def _embed(embed, tokens):
    return embed.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, norm, embed, targets, eps, control):
    """(best logit, logits of each row of ``targets`` (T, B, S), argmax) at
    every position."""
    h = _rmsnorm(x, norm.astype(jnp.float32), eps)
    logits = _mm(h, embed.astype(jnp.float32).T, control)
    chosen = jnp.take_along_axis(logits[None], targets[..., None], axis=-1)[..., 0]
    return logits.max(axis=-1), chosen, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _cfg_items(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def scores(params, cfg: dict, tokens, targets, control: bool = False):
    """Run the model over ``tokens`` (B, S) layer by layer; returns the
    best logit (B, S), the logits of the tokens in each row of ``targets``
    (T, B, S), and the argmax (B, S) at every position, as NumPy arrays."""
    pin = pout = None
    if "sable" in cfg:
        pats = patterns(cfg)
        pin = tuple(jnp.asarray(a) for a in pats["in"])
        pout = tuple(jnp.asarray(a) for a in pats["out"])
    eps = float(cfg["rms_norm_eps"])
    items = _cfg_items(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(params["embed"], tokens)
    (stack,) = params["groups"]
    n_layers = int(cfg["num_hidden_layers"])
    for layer in range(n_layers):
        p = jax.tree.map(lambda a: a[layer], stack["sub0"])
        x = _layer(x, p, pin, pout, items, control)
    best, chosen, top = _head(x, params["final_norm"], params["embed"],
                              jnp.asarray(targets, jnp.int32), eps, control)
    return np.asarray(best), np.asarray(chosen), np.asarray(top)
