"""Plain float32 reference for DeepSeek-V2 with multi-head latent attention
and shared plus routed experts (DeepSeek-V2-Lite), on one chip's share of
expert parallelism.

The mathematics follows DeepSeek-V2 (arXiv:2405.04434) and its published
``modeling_deepseek.py``: token embedding, then per layer

* ``x += Wo attn(q, k, v)``, with ``q = Wq n(x)`` (no query LoRA) split into
  ``q_nope`` and ``q_rope``; the latent ``c = n_kv(Wdkv n(x))[:kv_lora]``
  and the shared rope key ``kr = Wdkv n(x)[kv_lora:]``; ``k_nope | v =
  c Wukv`` per head; ``k = k_nope | kr``; rope on ``q_rope`` and ``kr``;
  causal softmax attention at scale ``(qk_nope + qk_rope)^-1/2 *
  mscale(factor, mscale_all_dim)^2``;
* YaRN rope (``rope_scaling``): with ``corr(r) = dim ln(L0 / (2 pi r)) /
  (2 ln base)``, ``low = floor(corr(beta_fast))``, ``high =
  ceil(corr(beta_slow))``, ``ramp_i = clip((i - low) / (high - low), 0,
  1)`` and ``inv_freq_i = base^(-2i/dim) (ramp_i / factor + 1 - ramp_i)``;
  cos and sin scaled by ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``, ``mscale(s, a) = 0.1 a ln s + 1``;
* the first ``first_k_dense_replace`` layers: ``x += W2 (silu(W1 n(x)) *
  (W3 n(x)))``; the others: a softmax router over the published
  ``n_routed_experts`` in float32, greedy top-k, the gates renormalised only
  with ``norm_topk_prob``, times ``routed_scaling_factor``; the routed
  result is summed over the experts this chip holds (the configuration's
  ``held_experts``), for every token routed to them; the shared experts
  (one SwiGLU of width ``n_shared_experts * moe_intermediate_size``) are
  added for every token;
* a final RMSNorm and logits against the untied ``lm_head``.

Departures, noted in the configuration's ``assumed``: the rope acts on the
half-split layout (dimensions ``i`` and ``i + dim/2`` rotate together),
where DeepSeek's code rotates interleaved pairs; on random weights that is
a fixed permutation of the rope columns of ``Wq`` and ``Wdkv``.

``scores`` runs the model layer by layer at float32 and ``highest`` matmul
precision, with K and V materialised and the queries and the logits in
blocks of positions, so that 8 rows of 2304 positions fit beside the
weights; with ``control=True`` the
operands of every weight matmul are rounded first to float8 (e4m3, one
scale per tensor).  The forward pass, the weights and the work count
import nothing of the program; ``program_config`` alone reaches it.

It keeps the serving driver's reference contract (``harness.py``):
``program_config``, ``make_params``, ``scores`` and ``work``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0
Q_BLOCK = 256  # query rows of one attention block
HEAD_BLOCK = 256  # positions of one block of logits (8 x 256 x 102,400 f32: 839 MB)


# ---------------------------------------------------------------------- #
# the program's configuration, and the work a token needs
# ---------------------------------------------------------------------- #
def program_config(cfg: dict):
    """The program's ModelConfig for this configuration file: the file's
    ``program`` entry (``module:function`` plus ``replace``) holding the
    file's ``held_experts``, checked against every published size, the held
    slice, the routing and the yarn numbers.  Raises on any departure."""
    prog = cfg["program"]
    mod_name, fn_name = prog["config"].split(":")
    base = getattr(importlib.import_module(mod_name), fn_name)()
    held = (int(cfg["held_experts"]["first"]), int(cfg["held_experts"]["count"]))
    mcfg = dataclasses.replace(base, **prog["replace"])
    mcfg = dataclasses.replace(mcfg, moe=dataclasses.replace(mcfg.moe, held=held))

    m, mc, y = mcfg.mla, mcfg.moe, mcfg.rope_scaling
    rs = cfg["rope_scaling"]
    n_dense = int(cfg["first_k_dense_replace"])
    published = int(cfg["published"]["n_routed_experts"])
    want = {
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"], "vocab_size": cfg["vocab_size"],
        "n_layers": cfg["num_hidden_layers"], "d_ff": cfg["intermediate_size"],
        "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
        "tie_embeddings": cfg["tie_word_embeddings"], "ffn_type": "swiglu",
        "attn_type": "mla", "param_dtype": cfg["precision"]["weights"],
        "compute_dtype": cfg["precision"]["compute"],
        "q_lora_rank": cfg["q_lora_rank"], "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_dim": cfg["qk_nope_head_dim"], "qk_rope_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "num_experts": published, "top_k": cfg["num_experts_per_tok"],
        "expert_d_ff": cfg["moe_intermediate_size"], "num_shared": cfg["n_shared_experts"],
        "shared_d_ff": cfg["moe_intermediate_size"],
        "held": (held[0], int(cfg["n_routed_experts"])),
        "norm_topk_prob": cfg["norm_topk_prob"], "router_f32": True,
        "layers": ((n_dense, "dense"), (int(cfg["num_hidden_layers"]) - n_dense, "moe")),
        "yarn": ("yarn", rs["factor"], rs["original_max_position_embeddings"],
                 rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"]),
    }
    got = {
        **{k: getattr(mcfg, k) for k in (
            "d_model", "n_heads", "n_kv_heads", "vocab_size", "n_layers", "d_ff",
            "rope_theta", "norm_eps", "tie_embeddings", "ffn_type", "attn_type",
            "param_dtype", "compute_dtype")},
        **{k: getattr(m, k) for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim", "v_head_dim")},
        "num_experts": mc.num_experts, "top_k": mc.top_k, "expert_d_ff": mc.d_ff,
        "num_shared": mc.num_shared, "shared_d_ff": mc.shared_d_ff, "held": mc.held,
        "norm_topk_prob": mc.norm_topk_prob, "router_f32": mc.router_f32,
        "layers": tuple((g.repeat, s.ffn) for g in mcfg.groups for s in g.layers),
        "yarn": None if y is None else (
            "yarn", y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.mscale, y.mscale_all_dim),
    }
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    mixers = {s.mixer for g in mcfg.groups for s in g.layers}
    fixed = {  # what this reference computes, and the program with it
        "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": 1, "moe_layer_freq": 1, "attention_bias": False,
        "hidden_act": "silu",
    }
    bad.update({k: (v, cfg[k]) for k, v in fixed.items() if cfg[k] != v})
    if held[0] < 0 or held[0] + held[1] > published:
        bad["held_experts"] = (held, published)
    if bad or mixers != {"mla"} or mcfg.enc_groups:
        raise ValueError(f"program config departs from {cfg['name']}: {bad}, mixers {mixers}")
    return mcfg


def work(cfg: dict):
    """``(per_token, per_key, per_logits_row)`` operations that ``mfu.serve``
    counts, two a multiply-add.  A token, in every layer: the MLA
    projections ``d H (nope + rope)`` (Wq), ``d (kv_lora + rope)`` (Wdkv),
    ``kv_lora H (nope + v)`` (Wukv: K and V up-projected once a token) and
    ``H v d`` (Wo); in the dense layers the FFN's ``3 d f``; in the MoE
    layers the router's ``d E``, the shared experts' ``3 d f_e n_shared``
    and the expected routed share of this chip's experts, ``top_k * held /
    E`` experts of ``3 d f_e``.  A key a token attends: ``H (nope + rope)``
    for the score and ``H v`` for the value, in every layer.  A logits row:
    ``d V``.  For DeepSeek-V2-Lite with 8 of 64 experts held, per layer MLA
    13,762,560, layer 0's FFN 67,239,936, a MoE layer 131,072 + 17,301,504
    + 0.75 x 8,650,752: (2,121,531,392, 276,480, 419,430,400)."""
    d, H, V = int(cfg["hidden_size"]), int(cfg["num_attention_heads"]), int(cfg["vocab_size"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    c, L = int(cfg["kv_lora_rank"]), int(cfg["num_hidden_layers"])
    n_dense = int(cfg["first_k_dense_replace"])
    fe, E = int(cfg["moe_intermediate_size"]), int(cfg["published"]["n_routed_experts"])
    mla = d * H * (dn + dr) + d * (c + dr) + c * H * (dn + dv) + H * dv * d
    dense = 3 * d * int(cfg["intermediate_size"])
    routed = 3 * d * fe * int(cfg["num_experts_per_tok"]) * int(cfg["n_routed_experts"])
    moe = d * E + 3 * d * fe * int(cfg["n_shared_experts"]) + routed // E
    per_token = 2 * (L * mla + n_dense * dense + (L - n_dense) * moe)
    return per_token, 2 * L * H * (dn + dr + dv), 2 * d * V


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf_law(name: str, cfg: dict):
    """(mean, std) of one leaf, by its place in the model."""
    d = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    fe = int(cfg["moe_intermediate_size"])
    parts = name.split("/")
    where = "/".join(parts[-2:])
    laws = {
        "embed": (0.0, 0.02),
        "lm_head": (0.0, d ** -0.5),
        "final_norm": (1.0, 0.1),
        "ln_mixer": (1.0, 0.1),
        "ln_ffn": (1.0, 0.1),
        "mixer/kvln": (1.0, 0.1),
        "mixer/wq": (0.0, d ** -0.5),
        "mixer/wdkv": (0.0, d ** -0.5),
        "mixer/wukv": (0.0, int(cfg["kv_lora_rank"]) ** -0.5),
        "mixer/wo": (0.0, (H * int(cfg["v_head_dim"])) ** -0.5),
        "ffn/w1": (0.0, d ** -0.5),
        "ffn/w3": (0.0, d ** -0.5),
        "ffn/w2": (0.0, int(cfg["intermediate_size"]) ** -0.5),
        "moe/router": (0.0, d ** -0.5),
        "moe/w1": (0.0, d ** -0.5),
        "moe/w3": (0.0, d ** -0.5),
        "moe/w2": (0.0, fe ** -0.5),
        "moe/sw1": (0.0, d ** -0.5),
        "moe/sw3": (0.0, d ** -0.5),
        "moe/sw2": (0.0, (int(cfg["n_shared_experts"]) * fe) ** -0.5),
    }
    for key in (where, parts[-1]):
        if key in laws:
            return laws[key]
    raise KeyError(f"the reference does not know parameter {name!r}")


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


def _mscale(scale: float, a: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * a * math.log(scale) + 1.0


def yarn(cfg: dict):
    """(inverse frequencies (dim/2,), cos/sin factor, attention scale, low,
    high) of the configuration's YaRN rope."""
    rs = cfg["rope_scaling"]
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    L0, factor = float(rs["original_max_position_embeddings"]), float(rs["factor"])

    def corr(r):
        return dim * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    inv = base ** (-2 * i / dim) * (ramp / factor + (1.0 - ramp))
    factor_cs = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    scale = (int(cfg["qk_nope_head_dim"]) + dim) ** -0.5 * _mscale(
        factor, rs["mscale_all_dim"]) ** 2
    return inv, factor_cs, scale, low, high


def _rope(x, inv, factor_cs):
    """Half-split rotary embedding over positions 0..S-1; x: (B, S, H, D)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * factor_cs)[None, :, None, :]
    sin = (jnp.sin(ang) * factor_cs)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, w1, w3, w2, control):
    return _mm(jax.nn.silu(_mm(h, w1, control)) * _mm(h, w3, control), w2, control)


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(x, p, cfg_items, control):
    cfg = dict(cfg_items)
    B, S, d = x.shape
    H, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    inv, factor_cs, scale = cfg["inv_freq"], cfg["cos_sin_factor"], cfg["scale"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    hi = jax.lax.Precision.HIGHEST

    # latent attention, K and V materialised
    mp = p["mixer"]
    h = _rmsnorm(x, f32(p["ln_mixer"]), eps)
    q = _mm(h, f32(mp["wq"]), control).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv, factor_cs)], axis=-1)
    ckv = _mm(h, f32(mp["wdkv"]), control)
    lat = _rmsnorm(ckv[..., :c], f32(mp["kvln"]), eps)
    kr = _rope(ckv[:, :, None, c:], inv, factor_cs)  # (B, S, 1, dr)
    kv = _mm(lat, f32(mp["wukv"]), control).reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (B, S, H, dr))], axis=-1)
    v = kv[..., dn:]
    outs = []
    for q0 in range(0, S, Q_BLOCK):  # queries in blocks, so that the scores fit
        qb = q[:, q0:q0 + Q_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=hi) * scale
        causal = jnp.arange(S)[None, :] <= (q0 + jnp.arange(qb.shape[1]))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                               precision=hi))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, H * dv)
    x = x + _mm(o, f32(mp["wo"]), control)

    h = _rmsnorm(x, f32(p["ln_ffn"]), eps)
    if "ffn" in p:  # a dense layer
        f = p["ffn"]
        return x + _swiglu(h, f32(f["w1"]), f32(f["w3"]), f32(f["w2"]), control)
    return x + moe(h, jax.tree.map(f32, p["moe"]), cfg, control)


def moe(h, mo, cfg: dict, control: bool = False):
    """The MoE layer's output for ``h`` (..., d): the shared experts, plus
    the routed part of the experts in ``mo`` (the held ones, the first of
    them the router's expert ``cfg["held_first"]``)."""
    probs = jax.nn.softmax(_mm(h, mo["router"], control), axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]
    y = _swiglu(h, mo["sw1"], mo["sw3"], mo["sw2"], control)
    first = cfg["held_first"]
    for e in range(mo["w1"].shape[0]):  # only the experts this chip holds
        w = jnp.sum(jnp.where(idx == first + e, gate, 0.0), axis=-1)
        y = y + w[..., None] * _swiglu(h, mo["w1"][e], mo["w3"][e], mo["w2"][e], control)
    return y


@jax.jit
def _embed(embed, tokens):
    return embed.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, norm, head, targets, eps, control):
    """(best logit, logits of each row of ``targets`` (T, B, S), argmax) at
    every position."""
    h = _rmsnorm(x, norm.astype(jnp.float32), eps)
    logits = _mm(h, head.astype(jnp.float32), control)
    chosen = jnp.take_along_axis(logits[None], targets[..., None], axis=-1)[..., 0]
    return logits.max(axis=-1), chosen, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def cfg_items(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    inv, factor_cs, scale, _, _ = yarn(cfg)
    return tuple((k, cfg[k]) for k in keys) + (
        ("inv_freq", tuple(float(f) for f in inv)), ("cos_sin_factor", float(factor_cs)),
        ("scale", float(scale)), ("held_first", int(cfg["held_experts"]["first"])))


def scores(params, cfg: dict, tokens, targets, control: bool = False):
    """Run the model over ``tokens`` (B, S) layer by layer; returns the
    best logit (B, S), the logits of the tokens in each row of ``targets``
    (T, B, S), and the argmax (B, S) at every position, as NumPy arrays."""
    items = cfg_items(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(params["embed"], tokens)
    for stack in params["groups"]:
        for layer in range(jax.tree.leaves(stack)[0].shape[0]):
            p = jax.tree.map(lambda a: a[layer], stack["sub0"])
            x = _layer(x, p, items, control)
    targets = jnp.asarray(targets, jnp.int32)
    eps = float(cfg["rms_norm_eps"])
    outs = [_head(x[:, s0:s0 + HEAD_BLOCK], params["final_norm"], params["lm_head"],
                  targets[..., s0:s0 + HEAD_BLOCK], eps, control)
            for s0 in range(0, x.shape[1], HEAD_BLOCK)]  # positions in blocks
    best, chosen, top = (np.concatenate([np.asarray(o[i]) for o in outs], axis=-1)
                         for i in range(3))
    return best, chosen, top
