"""Jitted public wrappers for the Pallas kernels.

``interpret=None`` auto-selects: compiled Mosaic on TPU, interpret mode
(Python-evaluated kernel body) elsewhere, so the same call sites work in
CPU tests and on real hardware.  Interpret mode is a CPU-only testing aid:
it is never chosen on a TPU, and asking for it there is an error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bsr_spmm import bsr_spmm_pallas
from .bsr_spmv import bsr_spmv_pallas

__all__ = ["bsr_spmm", "bsr_spmv"]


def _auto_interpret(interpret):
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is CPU-only; run compiled on a TPU")
    return bool(interpret)


@functools.partial(jax.jit, static_argnames=("m_pad", "bn", "interpret"))
def _spmm_jit(tiles, row_ids, col_ids, x, *, m_pad, bn, interpret):
    n = x.shape[1]
    n_pad = -(-n // bn) * bn
    if n_pad != n:
        x = jnp.pad(x, ((0, 0), (0, n_pad - n)))
    y = bsr_spmm_pallas(
        tiles, row_ids, col_ids, x, m_pad=m_pad, bn=bn, interpret=interpret
    )
    return y[:, :n]


def bsr_spmm(tiles, row_ids, col_ids, x, *, m_pad, bn=128, interpret=None):
    """Block-sparse SpMM: y (m_pad, n) from uniform tiles + tables."""
    bn = min(bn, max(int(x.shape[1]), 1))
    return _spmm_jit(
        tiles,
        row_ids,
        col_ids,
        x,
        m_pad=m_pad,
        bn=bn,
        interpret=_auto_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("m_pad", "interpret"))
def _spmv_jit(tiles, row_ids, col_ids, x, *, m_pad, interpret):
    return bsr_spmv_pallas(
        tiles, row_ids, col_ids, x, m_pad=m_pad, interpret=interpret
    )


def bsr_spmv(tiles, row_ids, col_ids, x, *, m_pad, interpret=None):
    """Block-sparse SpMV: y (m_pad,) from uniform tiles + tables."""
    return _spmv_jit(
        tiles, row_ids, col_ids, x, m_pad=m_pad, interpret=_auto_interpret(interpret)
    )
