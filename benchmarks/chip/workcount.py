"""The work a call or a token needs, counted from the configuration alone.

These counts are the numerators of the roofline and utilisation metrics.
They count what the mathematics needs, never what an implementation does:
padding, tiles, gathers and recomputation are not counted, so the same
work is credited whatever backend, tile or layout computes it.
"""
from __future__ import annotations


def staged_call(structure, n_cols, value_bytes: int = 4) -> dict:
    """One ``y = A x`` over a VBR matrix: every stored value read once, x
    read once, y written once, two operations per stored value and column."""
    cols = 1 if n_cols is None else int(n_cols)
    m, k = structure.shape
    nnz = structure.stored_nnz
    return {
        "bytes": value_bytes * (nnz + k * cols + m * cols),
        "flops": 2 * nnz * cols,
    }


def roofline_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and which bound
    (``bytes`` or ``flops``) sets it."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def decoder_flops(cfg: dict, ffn_weights: int):
    """Operations of a decoder-only transformer with GQA attention whose
    FFN multiplies ``ffn_weights`` weights a token in each layer (the
    configuration's reference counts them: stored tiles only, where the
    matrices are block-sparse).

    Returns ``(per_token, per_key, per_logits_row)``: the operations of one
    token through every layer's projections and FFN, the attention
    operations per cached key that a token attends (QK and PV over all
    heads and layers), and the unembedding of one logits row."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    layers = int(cfg["num_hidden_layers"])
    attn_proj = d * h * dh + 2 * d * kv * dh + h * dh * d
    per_token = 2 * layers * (attn_proj + int(ffn_weights))
    per_key = 4 * layers * h * dh
    per_logits_row = 2 * d * int(cfg["vocab_size"])
    return per_token, per_key, per_logits_row
