"""Pallas TPU kernel: block-sparse SpMM over uniformized VBR tiles.

The staged structure (tile -> (row, col) tables from ``core.uniformize``)
is passed as *scalar-prefetch* operands: Mosaic reads them from SMEM to
compute the DMA schedule, which is exactly the paper's Stage-1 "constant
bounds baked into the code", in TPU form — the HLO/kernel is O(1) in the
number of blocks, the tables are data.

Grid layout: ``(n_j, nb)`` with the dense-column tile ``j`` OUTER and the
block index ``b`` INNER.  Tiles are sorted by output row tile, so all
blocks contributing to one output tile are consecutive grid steps: the
output block stays resident in VMEM and is accumulated, initialized on
first visit (``row changes => new accumulation``).  This is the standard
TPU block-sparse matmul schedule; the MXU sees only dense (tm, tk) x
(tk, bn) products — "compute over some zeros" in its purest form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(row_ids, col_ids, tiles_ref, x_ref, y_ref, *, acc_dtype):
    b = pl.program_id(1)
    row = row_ids[b]
    prev_row = row_ids[jnp.maximum(b - 1, 0)]
    is_first = jnp.logical_or(b == 0, prev_row != row)

    @pl.when(is_first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    acc = jnp.dot(
        tiles_ref[0].astype(acc_dtype),
        x_ref[...].astype(acc_dtype),
        preferred_element_type=acc_dtype,
    )
    y_ref[...] += acc.astype(y_ref.dtype)


def bsr_spmm_pallas(
    tiles: jax.Array,  # (nb, tm, tk)
    row_ids: jax.Array,  # (nb,) int32, sorted
    col_ids: jax.Array,  # (nb,) int32
    x: jax.Array,  # (k_pad, n) with n % bn == 0
    *,
    m_pad: int,
    bn: int,
    interpret: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    nb, tm, tk = tiles.shape
    k_pad, n = x.shape
    assert n % bn == 0, f"n={n} must be a multiple of bn={bn}"
    n_j = n // bn

    grid = (n_j, nb)
    kernel = functools.partial(_kernel, acc_dtype=acc_dtype)

    in_specs = [
        pl.BlockSpec((1, tm, tk), lambda j, b, rows, cols: (b, 0, 0)),
        pl.BlockSpec((tk, bn), lambda j, b, rows, cols: (cols[b], j)),
    ]
    out_spec = pl.BlockSpec((tm, bn), lambda j, b, rows, cols: (rows[b], j))
    out_shape = jax.ShapeDtypeStruct((m_pad, n), x.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(row_ids, col_ids, tiles, x)
