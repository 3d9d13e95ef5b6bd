"""Pallas TPU kernel: block-sparse SpMV over uniformized VBR tiles.

SpMV is VPU-bound (no MXU): each grid step multiplies one (tm, tk) tile by
a tk-slice of x and accumulates a tm-slice of y.  Mosaic wants the last two
dims of every block divisible by (8, 128) or equal to the array's, so x is
viewed as a (1, k_pad) row read in (1, tk) blocks and y as an (m_pad, 1)
column written in (tm, 1) blocks: the lane reduction of a (tm, tk) tile
lands as a (tm, 1) column, which is the y block's own layout.  Same
sorted-rows accumulate-in-VMEM schedule as SpMM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(row_ids, col_ids, tiles_ref, x_ref, y_ref, *, acc_dtype):
    b = pl.program_id(0)
    row = row_ids[b]
    prev_row = row_ids[jnp.maximum(b - 1, 0)]
    is_first = jnp.logical_or(b == 0, prev_row != row)

    @pl.when(is_first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    tile = tiles_ref[0].astype(acc_dtype)  # (tm, tk)
    xv = x_ref[...].astype(acc_dtype)  # (1, tk)
    acc = jnp.sum(tile * xv, axis=1, keepdims=True)  # (tm, 1), VPU lane reduce
    y_ref[...] += acc.astype(y_ref.dtype)


def bsr_spmv_pallas(
    tiles: jax.Array,  # (nb, tm, tk)
    row_ids: jax.Array,  # (nb,) int32, sorted
    col_ids: jax.Array,  # (nb,) int32
    x: jax.Array,  # (k_pad,)
    *,
    m_pad: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    nb, tm, tk = tiles.shape
    (k_pad,) = x.shape
    assert k_pad % tk == 0 and m_pad % tm == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, tm, tk), lambda b, rows, cols: (b, 0, 0)),
            pl.BlockSpec((1, tk), lambda b, rows, cols: (0, cols[b])),
        ],
        out_specs=pl.BlockSpec((tm, 1), lambda b, rows, cols: (rows[b], 0)),
    )
    y = pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, 1), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(row_ids, col_ids, tiles, x.reshape(1, k_pad))
    return y.reshape(m_pad)
