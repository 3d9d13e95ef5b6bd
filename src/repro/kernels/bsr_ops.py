"""The fixed-block sparse product family over ``BlockMatrix``: dsd/dds/sdd.

STK's op family (SNIPPETS.md §1) in Pallas form, for structures that
change every call (per-batch MoE topologies) — no inspection, no staging,
no plan cache.  All three ops take the blocked-CSR-COO arrays as *runtime
data* (scalar-prefetch operands on TPU), so one compiled program serves
every topology of the same ``nnz_max`` bound:

  ``dsd(S, x)``      dense (M,N)  = sparse (M,K) @ dense (K,N)
  ``dds(x, S)``      dense (M,N)  = dense (M,K)  @ sparse (K,N)
  ``sdd(a, b, T)``   sparse       = dense (M,K)  @ dense (K,N), computed
                     only at ``T``'s block topology (sparse *output*)

Each op has a grouped-einsum reference implementation (gather + batched
block matmul + scatter-add, ``backend='grouped'``, the portable/CPU path)
and a Pallas kernel (``backend='pallas'``) reusing the scalar-prefetch
grid schedule of ``bsr_spmm``.  ``backend='auto'`` picks pallas on TPU.

Every op carries a ``custom_vjp`` whose backward passes are themselves
members of the family — the closure property that makes dropless-MoE
training run entirely on these kernels::

  d dsd(S, x) / dx    = dsd(S^T, g)         d/dS    = sdd(g, x^T, S)
  d dds(x, S) / dx    = dds(g, S^T)         d/dS    = sdd(x^T, g, S)
  d sdd(a, b, T) / da = dsd(g_T, b^T)       d/db    = dds(a^T, g_T)

Padding slots (``row == n_block_rows``) ride along as zero blocks:
scatters drop them, gathers read clamped coordinates against zero data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sparse.block_csr import BlockMatrix
from .ops import _auto_interpret, bsr_spmm

__all__ = ["dsd", "dds", "sdd"]


def _resolve(backend: str, interpret):
    interpret = _auto_interpret(interpret)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "grouped"
    if backend not in ("grouped", "pallas"):
        raise ValueError(f"unknown bsr_ops backend {backend!r}")
    return backend, interpret


def _f0(a):
    """float0 cotangent for an integer-valued primal input."""
    return np.zeros(a.shape, jax.dtypes.float0)


# ---------------------------------------------------------------------- #
# sdd pallas kernel: one output block per grid row, K tiled inner
# ---------------------------------------------------------------------- #
def _sdd_kernel(row_ids, col_ids, a_ref, b_ref, o_ref, *, acc_dtype):
    del row_ids, col_ids  # consumed by the index maps
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jnp.dot(
        a_ref[...].astype(acc_dtype),
        b_ref[...].astype(acc_dtype),
        preferred_element_type=acc_dtype,
    )
    o_ref[...] += acc[None].astype(o_ref.dtype)


def _pick_bk(K: int) -> int:
    if K <= 512:
        return K
    for t in (512, 256, 128):
        if K % t == 0:
            return t
    return K


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def _sdd_pallas(a, b, rows, cols, *, bm, bn, interpret):
    """(nnz, bm, bn) blocks of a @ b at (rows, cols); coordinates must be
    pre-clamped in-bounds (invalid slots are zeroed by the caller)."""
    nb = rows.shape[0]
    K = a.shape[1]
    bk = _pick_bk(K)
    grid = (nb, K // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k, rows, cols: (rows[i], k)),
            pl.BlockSpec((bk, bn), lambda i, k, rows, cols: (k, cols[i])),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, k, rows, cols: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_sdd_kernel, acc_dtype=jnp.float32),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, bm, bn), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(rows, cols, a, b)


# ---------------------------------------------------------------------- #
# dsd: dense = sparse @ dense
# ---------------------------------------------------------------------- #
def _dsd_impl(spec, data, rows, cols, x):
    (M, K), (bm, bk), backend, interpret = spec
    Rb, Kb = M // bm, K // bk
    N = x.shape[1]
    if backend == "pallas":
        # padded slots target the extra (Rb+1)-th block row, sliced off;
        # their zero data makes the clamped column reads harmless
        y = bsr_spmm(
            data, rows, jnp.minimum(cols, Kb - 1), x,
            m_pad=(Rb + 1) * bm, interpret=interpret,
        )[:M]
        # block rows with no blocks are never visited by the accumulation
        # schedule — zero them explicitly
        covered = jnp.zeros((Rb,), bool).at[rows].set(True, mode="drop")
        return jnp.where(jnp.repeat(covered, bm)[:, None], y, 0)
    xg = x.reshape(Kb, bk, N)[jnp.minimum(cols, Kb - 1)]  # (nnz, bk, N)
    part = jnp.einsum("bmk,bkn->bmn", data, xg)
    y = jnp.zeros((Rb, bm, N), part.dtype).at[rows].add(part, mode="drop")
    return y.reshape(M, N)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dsd_core(spec, data, rows, cols, x):
    return _dsd_impl(spec, data, rows, cols, x)


def _dsd_fwd(spec, data, rows, cols, x):
    return _dsd_impl(spec, data, rows, cols, x), (data, rows, cols, x)


def _dsd_bwd(spec, res, g):
    data, rows, cols, x = res
    (M, K), (bm, bk), backend, interpret = spec
    sp = BlockMatrix.from_coo((M, K), (bm, bk), data, rows, cols)
    dx = dsd(sp.transpose(), g, backend=backend, interpret=interpret)
    dsp = sdd(g, x.T, sp, backend=backend, interpret=interpret)
    return (
        dsp.data.astype(data.dtype),
        _f0(rows),
        _f0(cols),
        dx.astype(x.dtype),
    )


_dsd_core.defvjp(_dsd_fwd, _dsd_bwd)


def dsd(sp: BlockMatrix, x: jnp.ndarray, *, backend: str = "auto",
        interpret=None) -> jnp.ndarray:
    """dense (M, N) = sparse (M, K) @ dense (K, N)."""
    assert x.ndim == 2 and x.shape[0] == sp.shape[1], (
        f"dsd: x {x.shape} does not match sparse {sp.shape}"
    )
    backend, interpret = _resolve(backend, interpret)
    spec = (tuple(sp.shape), tuple(sp.block), backend, interpret)
    return _dsd_core(spec, sp.data, sp.row_indices, sp.column_indices, x)


# ---------------------------------------------------------------------- #
# dds: dense = dense @ sparse
# ---------------------------------------------------------------------- #
def _dds_impl(spec, x, data, rows, cols):
    (K, N), (bm, bn), backend, interpret = spec
    Kb, Nb = K // bm, N // bn
    M = x.shape[0]
    if backend == "pallas":
        # x @ S == (S^T @ x^T)^T — reuse the dsd schedule on the transpose
        spT = BlockMatrix.from_coo((K, N), (bm, bn), data, rows, cols
                                   ).transpose()
        return dsd(spT, x.T, backend=backend, interpret=interpret).T
    xg = x.reshape(M, Kb, bm)[:, jnp.minimum(rows, Kb - 1)]  # (M, nnz, bm)
    part = jnp.einsum("mbt,btk->mbk", xg, data)
    # invalid slots scatter zeros into block-col 0 — harmless
    y = jnp.zeros((M, Nb, bn), part.dtype).at[:, cols].add(part, mode="drop")
    return y.reshape(M, N)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dds_core(spec, x, data, rows, cols):
    return _dds_impl(spec, x, data, rows, cols)


def _dds_fwd(spec, x, data, rows, cols):
    return _dds_impl(spec, x, data, rows, cols), (x, data, rows, cols)


def _dds_bwd(spec, res, g):
    x, data, rows, cols = res
    (K, N), (bm, bn), backend, interpret = spec
    sp = BlockMatrix.from_coo((K, N), (bm, bn), data, rows, cols)
    dx = dds(g, sp.transpose(), backend=backend, interpret=interpret)
    dsp = sdd(x.T, g, sp, backend=backend, interpret=interpret)
    return (
        dx.astype(x.dtype),
        dsp.data.astype(data.dtype),
        _f0(rows),
        _f0(cols),
    )


_dds_core.defvjp(_dds_fwd, _dds_bwd)


def dds(x: jnp.ndarray, sp: BlockMatrix, *, backend: str = "auto",
        interpret=None) -> jnp.ndarray:
    """dense (M, N) = dense (M, K) @ sparse (K, N)."""
    assert x.ndim == 2 and x.shape[1] == sp.shape[0], (
        f"dds: x {x.shape} does not match sparse {sp.shape}"
    )
    backend, interpret = _resolve(backend, interpret)
    spec = (tuple(sp.shape), tuple(sp.block), backend, interpret)
    return _dds_core(spec, x, sp.data, sp.row_indices, sp.column_indices)


# ---------------------------------------------------------------------- #
# sdd: sparse output = dense @ dense under a topology mask
# ---------------------------------------------------------------------- #
def _sdd_impl(spec, a, b, rows, cols):
    (M, N), (bm, bn), backend, interpret = spec
    Rb, Cb = M // bm, N // bn
    valid = rows < Rb
    rc = jnp.minimum(rows, Rb - 1)
    cc = jnp.minimum(cols, Cb - 1)
    if backend == "pallas":
        data = _sdd_pallas(a, b, rc, cc, bm=bm, bn=bn, interpret=interpret)
    else:
        ag = a.reshape(Rb, bm, a.shape[1])[rc]  # (nnz, bm, K)
        bg = b.reshape(b.shape[0], Cb, bn).transpose(1, 0, 2)[cc]
        data = jnp.einsum("bmk,bkn->bmn", ag, bg)
    return jnp.where(valid[:, None, None], data, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sdd_core(spec, a, b, rows, cols):
    return _sdd_impl(spec, a, b, rows, cols)


def _sdd_fwd(spec, a, b, rows, cols):
    return _sdd_impl(spec, a, b, rows, cols), (a, b, rows, cols)


def _sdd_bwd(spec, res, g):
    a, b, rows, cols = res
    (M, N), (bm, bn), backend, interpret = spec
    g_sp = BlockMatrix.from_coo((M, N), (bm, bn), g, rows, cols)
    da = dsd(g_sp, b.T, backend=backend, interpret=interpret)
    db = dds(a.T, g_sp, backend=backend, interpret=interpret)
    return da.astype(a.dtype), db.astype(b.dtype), _f0(rows), _f0(cols)


_sdd_core.defvjp(_sdd_fwd, _sdd_bwd)


def sdd(a: jnp.ndarray, b: jnp.ndarray, topo: BlockMatrix, *,
        backend: str = "auto", interpret=None) -> BlockMatrix:
    """sparse (M, N) = dense (M, K) @ dense (K, N), computed only at
    ``topo``'s blocks.  Returns a BlockMatrix sharing ``topo``'s
    structure arrays (same slot order — elementwise ops on ``.data``
    stay aligned across same-topology products)."""
    (M, N) = topo.shape
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[0], (
        f"sdd: inner dims {a.shape} @ {b.shape}"
    )
    assert a.shape[0] == M and b.shape[1] == N, (
        f"sdd: output {a.shape[0]}x{b.shape[1]} vs topology {topo.shape}"
    )
    backend, interpret = _resolve(backend, interpret)
    spec = (tuple(topo.shape), tuple(topo.block), backend, interpret)
    data = _sdd_core(spec, a, b, topo.row_indices, topo.column_indices)
    return BlockMatrix(
        tuple(topo.shape), tuple(topo.block), data,
        topo.row_indices, topo.column_indices, topo.offsets,
    )
