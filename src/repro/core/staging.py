"""SABLE staging engine: Stage 0 -> Stage 1 -> Stage 2 (paper Fig. 5).

Stage 0  the block iterator walks the VBR indirection arrays (pure Python,
         everything concrete) and runs the user's DSL op once per block,
         recording a loop-nest IR with constant bounds/offsets.
Stage 1  the IR is lowered to a specialized JAX program.  Backends:

           'unrolled'  one slice+dot per block, paper-faithful codegen
                       (HLO size O(#blocks), like SABLE's generated C),
           'grouped'   blocks grouped by shape class; one gather + batched
                       einsum + scatter-add per class (HLO size O(#classes)),
           'pallas'    tile-uniformized Pallas TPU kernel with
                       scalar-prefetched block tables (HLO size O(1)),
           'gather'    generic vectorized evaluation of ANY DSL op
                       (the extensibility story of Section IV-A),
           'dia_hybrid' dense diagonals DIA-style + staged remainder,
                       SpMV-only (kernels/dia_hybrid.py, Fukaya et al.),
           'auto'      grouped (CPU/XLA) — pallas on TPU,
           'autotune'  measured choice: micro-benchmark the candidates via
                       ``core.autotune`` and persist the winner on disk
                       (``core.cache``) keyed by structure hash + device.

Stage 2  XLA/Mosaic compiles the specialized program.  Executables are
         cached keyed by the *structure hash* — values are runtime inputs,
         so one binary serves every matrix with the same pattern
         (compile-once / run-many, Section III).

The density-threshold hybrid (paper Listings 3/4, Figs 8/11) routes blocks
whose fill is below ``density_threshold`` to an unrolled COO tail instead of
dense loops, given staging-time ``value_hints``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import vbr as vbrlib
from .backends import BlockMatmul, match_block_matmul, run_vectorized
from .dsl import ArrayVal, RepRange, stage_op
from .ops_dsl import ArrayView, spmm_op, spmv_op
from .uniformize import TiledPattern, uniformize

__all__ = [
    "StagingOptions",
    "StagedKernel",
    "stage_spmv",
    "stage_spmm",
    "stage_block_op",
    "partition_block_rows",
    "clear_cache",
    "cache_info",
]


@dataclasses.dataclass(frozen=True)
class StagingOptions:
    backend: str = "auto"  # auto|autotune|unrolled|grouped|bucketed|pallas|gather
    density_threshold: float = 0.0  # blocks below -> COO tail (needs hints)
    tile: tuple = (8, 128)  # pallas (tm, tk)
    spmm_bn: int = 128  # pallas N-tile
    interpret: Optional[bool] = None  # pallas interpret mode (None=auto)
    prepack: bool = False  # caller passes prepacked tiles to __call__
    dtype: object = None  # cast values (None = keep)

    def key(self) -> tuple:
        return (
            self.backend,
            self.density_threshold,
            self.tile,
            self.spmm_bn,
            self.interpret,
            self.prepack,
            str(self.dtype),
        )


def _resolve_backend(backend: str) -> str:
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "grouped"


# ---------------------------------------------------------------------- #
# Stage-0 inspection
# ---------------------------------------------------------------------- #
def _inspect(vbr: vbrlib.VBR, kind: str, n_cols: Optional[int]) -> list[BlockMatmul]:
    """Run the DSL op over every block (the paper's block iterator) and
    pattern-match the recorded IR into BlockMatmul descriptors."""
    val_av = ArrayVal("val")
    x_av = ArrayVal("x")
    y_av = ArrayVal("y")
    descs: list[BlockMatmul] = []
    for t in vbr.blocks():
        rr = RepRange(t.row_start, t.row_end)
        cr = RepRange(t.col_start, t.col_end)
        view = ArrayView(val_av, t.val_offset)
        if kind == "spmv":
            prog = stage_op(spmv_op, rr, cr, view, x_av, y_av)
        else:
            prog = stage_op(spmm_op, rr, cr, RepRange(0, n_cols), view, x_av, y_av)
        d = match_block_matmul(prog)
        if d is None:  # the canonical ops always match
            raise RuntimeError("op did not match the block-matmul pattern")
        descs.append(d)
    return descs


def _split_by_density(
    descs: list[BlockMatmul],
    hints: Optional[np.ndarray],
    threshold: float,
) -> tuple[list[BlockMatmul], list[BlockMatmul]]:
    if threshold <= 0.0 or hints is None:
        return descs, []
    dense, sparse = [], []
    for d in descs:
        blk = hints[d.val_off : d.val_off + d.h * d.w]
        density = np.count_nonzero(blk) / max(blk.size, 1)
        (dense if density >= threshold else sparse).append(d)
    return dense, sparse


def _coo_from_hints(descs: list[BlockMatmul], hints: np.ndarray):
    """Unrolled (Listing 3/4) path: bake the nonzero coordinates of the
    low-density blocks at staging time."""
    rows, cols, vidx = [], [], []
    for d in descs:
        blk = hints[d.val_off : d.val_off + d.h * d.w]
        (nz,) = np.nonzero(blk)
        rows.append(d.row_start + (nz % d.h))
        cols.append(d.col_start + (nz // d.h))
        vidx.append(d.val_off + nz)
    if not rows:
        return None
    return (
        np.concatenate(rows).astype(np.int32),
        np.concatenate(cols).astype(np.int32),
        np.concatenate(vidx).astype(np.int32),
    )


# ---------------------------------------------------------------------- #
# Shape-class grouping (Stage-1 'grouped' backend)
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class _ShapeClass:
    h: int
    w: int
    vidx: np.ndarray  # (nb, h*w) int32 gather map into val (+1; 0=pad zero)
    xrow: np.ndarray  # (nb, w) int32 (+1; 0 = pad zero)
    yrow: np.ndarray  # (nb, h) int32 (invalid rows point past m => dropped)
    padded: bool


def _next_bucket(n: int) -> int:
    """Round up to 1.25x-spaced buckets: 8,10,12,15,18,22,27,33,41,...
    (<=25% padding per dim; ~1.25x classes vs 1.5x spacing but less
    wasted compute — measured the better trade on both backends)."""
    b = 8
    while b < n:
        b += max(b // 4, 2)
    return b


def _group_by_shape(
    descs: list[BlockMatmul], m_rows: int, bucket: bool = False
) -> list[_ShapeClass]:
    """Group blocks into shape classes.  With ``bucket=True`` (the
    'bucketed' backend), block dims are rounded UP to a coarse bucket grid
    and padded with zeros — trading a bounded amount of compute-over-zeros
    (the paper's own thesis) for O(#buckets) kernels instead of O(#shapes)
    on non-uniformly split matrices."""
    groups: dict[tuple, list[BlockMatmul]] = {}
    for d in descs:
        key = (
            (_next_bucket(d.h), _next_bucket(d.w)) if bucket else (d.h, d.w)
        )
        groups.setdefault(key, []).append(d)
    out = []
    for (h, w), ds in sorted(groups.items()):
        nb = len(ds)
        vidx = np.zeros((nb, h * w), dtype=np.int64)
        xrow = np.zeros((nb, w), dtype=np.int64)
        yrow = np.full((nb, h), m_rows, dtype=np.int64)  # OOB => drop
        for i, d in enumerate(ds):
            # col-major block layout: idx = col*d.h + row (+1 sentinel shift)
            rr = np.arange(d.h)
            cc = np.arange(d.w)
            g = (d.val_off + cc[None, :] * d.h + rr[:, None] + 1)  # (dh, dw)
            v2 = vidx[i].reshape(w, h).T  # view as (h, w) row-major
            v2[: d.h, : d.w] = g
            vidx[i] = v2.T.reshape(-1)
            xrow[i, : d.w] = d.col_start + cc + 1
            yrow[i, : d.h] = d.row_start + rr
        out.append(
            _ShapeClass(
                h=h, w=w,
                vidx=vidx.astype(np.int32),
                xrow=xrow.astype(np.int32),
                yrow=yrow.astype(np.int32),
                padded=True,
            )
        )
    return out


# ---------------------------------------------------------------------- #
# Staged kernel object
# ---------------------------------------------------------------------- #
class StagedKernel:
    """A compiled pattern-specialized sparse kernel: ``fn(val, x) -> y``.

    ``val`` is the VBR value array (runtime), ``x`` the dense operand.
    Metadata (inspection time, #classes, padding fraction) is recorded for
    the paper's inspection-time and codegen-variant experiments.
    """

    def __init__(self, kind, vbr, opts: StagingOptions, hints=None, n_cols=None):
        t0 = time.perf_counter()
        self.kind = kind
        self.opts = opts
        self.backend = _resolve_backend(opts.backend)
        self.m, self.k = vbr.shape
        self.n_cols = n_cols
        self.structure_hash = vbrlib.structure_hash(vbr)
        descs = _inspect(vbr, kind, n_cols)
        self.num_blocks = len(descs)
        dense_descs, sparse_descs = _split_by_density(
            descs, hints, opts.density_threshold
        )
        self.coo = _coo_from_hints(sparse_descs, hints) if sparse_descs else None
        self.descs = dense_descs
        self.classes = None
        self.tiled: Optional[TiledPattern] = None
        if self.backend in ("grouped", "bucketed"):
            self.classes = _group_by_shape(
                dense_descs, self.m, bucket=self.backend == "bucketed"
            )
        elif self.backend == "pallas":
            tm, tk = opts.tile
            self.tiled = uniformize(
                dense_descs, self.m, self.k, vbr.rpntr, vbr.cpntr, tm, tk
            )
        elif self.backend == "gather":
            self._gather_vbr = vbr
        self.stage0_time = time.perf_counter() - t0
        self.compile_time = 0.0
        fn = self._build()
        # a stable program name: jit_sable_<kind>_<backend> in a trace
        fn.__name__ = fn.__qualname__ = f"sable_{kind}_{self.backend}"
        self._fn = jax.jit(fn)

    # ------------------------------------------------------------------ #
    def _build(self) -> Callable:
        kind, backend = self.kind, self.backend
        m = self.m
        coo = self.coo
        dtype_cast = self.opts.dtype

        def add_coo(y, val, x):
            if coo is None:
                return y
            rows, cols, vidx = (jnp.asarray(a) for a in coo)
            with jax.named_scope("sable.pack"):
                v = val[vidx]
            with jax.named_scope("sable.multiply"):
                if kind == "spmv":
                    return y.at[rows].add(v * x[cols])
                return y.at[rows].add(v[:, None] * x[cols])

        if backend == "unrolled":
            descs = self.descs

            def fn(val, x):
                if dtype_cast is not None:
                    val, x = val.astype(dtype_cast), x.astype(dtype_cast)
                y = jnp.zeros(self._out_shape(x), dtype=x.dtype)
                for d in descs:  # one slice+dot per block (paper codegen)
                    with jax.named_scope("sable.pack"):
                        blk = val[d.val_off : d.val_off + d.h * d.w]
                        a = blk.reshape(d.w, d.h).T
                    with jax.named_scope("sable.multiply"):
                        xs = x[d.col_start : d.col_end]
                        y = y.at[d.row_start : d.row_end].add(a @ xs)
                return add_coo(y, val, x)

            return fn

        if backend in ("grouped", "bucketed"):
            classes = self.classes

            def fn(val, x):
                if dtype_cast is not None:
                    val, x = val.astype(dtype_cast), x.astype(dtype_cast)
                # sentinel slot 0 = zero (padding reads); OOB rows dropped
                with jax.named_scope("sable.pack"):
                    val1 = jnp.concatenate([jnp.zeros((1,), val.dtype), val])
                if kind == "spmv":
                    x1 = jnp.concatenate([jnp.zeros((1,), x.dtype), x])
                else:
                    x1 = jnp.concatenate(
                        [jnp.zeros((1, x.shape[1]), x.dtype), x], axis=0
                    )
                y = jnp.zeros(self._out_shape(x), dtype=x.dtype)
                for c in classes:
                    with jax.named_scope("sable.pack"):
                        a = val1[c.vidx].reshape(-1, c.w, c.h)  # col-major blocks
                    with jax.named_scope("sable.multiply"):
                        if kind == "spmv":
                            part = jnp.einsum("bwh,bw->bh", a, x1[c.xrow])
                        else:
                            part = jnp.einsum("bwh,bwn->bhn", a, x1[c.xrow])
                        y = y.at[c.yrow].add(part, mode="drop")
                return add_coo(y, val, x)

            return fn

        if backend == "pallas":
            from ..kernels import ops as kops

            tiled = self.tiled
            interpret = self.opts.interpret
            prepack = self.opts.prepack
            bn = self.opts.spmm_bn

            def fn(val, x):
                if dtype_cast is not None:
                    val, x = val.astype(dtype_cast), x.astype(dtype_cast)
                if prepack:
                    tiles = val  # caller already packed via self.pack()
                else:
                    with jax.named_scope("sable.pack"):
                        v1 = jnp.concatenate(
                            [jnp.zeros((1,), x.dtype), val.astype(x.dtype)]
                        )
                        tiles = v1[jnp.asarray(tiled.val_gather)].reshape(
                            tiled.n_tiles, tiled.tm, tiled.tk
                        )
                with jax.named_scope("sable.multiply"):
                    if kind == "spmv":
                        x1 = jnp.concatenate([jnp.zeros((1,), x.dtype), x])
                        xp = x1[jnp.asarray(tiled.x_src)]
                        yp = kops.bsr_spmv(
                            tiles,
                            jnp.asarray(tiled.row_ids),
                            jnp.asarray(tiled.col_ids),
                            xp,
                            m_pad=tiled.m_pad,
                            interpret=interpret,
                        )
                    else:
                        x1 = jnp.concatenate(
                            [jnp.zeros((1, x.shape[1]), x.dtype), x], axis=0
                        )
                        xp = x1[jnp.asarray(tiled.x_src)]
                        yp = kops.bsr_spmm(
                            tiles,
                            jnp.asarray(tiled.row_ids),
                            jnp.asarray(tiled.col_ids),
                            xp,
                            m_pad=tiled.m_pad,
                            bn=bn,
                            interpret=interpret,
                        )
                    y = yp[jnp.asarray(tiled.y_src)]
                coo_y = add_coo(jnp.zeros_like(y), val.reshape(-1), x) if coo else None
                return y if coo_y is None else y + coo_y

            return fn

        if backend == "gather":
            vbr = self._gather_vbr
            n_cols = self.n_cols

            def fn(val, x):
                if dtype_cast is not None:
                    val, x = val.astype(dtype_cast), x.astype(dtype_cast)
                if kind == "spmv":
                    y = jnp.zeros((m,), dtype=x.dtype)
                    env = {"val": val, "x": x, "y": y}
                    val_av, x_av, y_av = (
                        ArrayVal("val"),
                        ArrayVal("x"),
                        ArrayVal("y"),
                    )
                    for t in vbr.blocks():
                        prog = stage_op(
                            spmv_op,
                            RepRange(t.row_start, t.row_end),
                            RepRange(t.col_start, t.col_end),
                            ArrayView(val_av, t.val_offset),
                            x_av,
                            y_av,
                        )
                        env = run_vectorized(prog, env)
                    return env["y"]
                # spmm via flattened row-major x/y (paper's layout)
                y = jnp.zeros((m * n_cols,), dtype=x.dtype)
                env = {"val": val, "x": x.reshape(-1), "y": y}
                val_av, x_av, y_av = ArrayVal("val"), ArrayVal("x"), ArrayVal("y")
                for t in vbr.blocks():
                    prog = stage_op(
                        spmm_op,
                        RepRange(t.row_start, t.row_end),
                        RepRange(t.col_start, t.col_end),
                        RepRange(0, n_cols),
                        ArrayView(val_av, t.val_offset),
                        x_av,
                        y_av,
                    )
                    env = run_vectorized(prog, env)
                return env["y"].reshape(m, n_cols)

            return fn

        raise ValueError(f"unknown backend {backend}")

    def _out_shape(self, x):
        if self.kind == "spmv":
            return (self.m,)
        return (self.m, x.shape[1])

    # ------------------------------------------------------------------ #
    def pack(self, val: jnp.ndarray) -> jnp.ndarray:
        """Prepack the runtime values into tiles (amortized across calls)."""
        assert self.tiled is not None, "pack() is for the pallas backend"
        with jax.named_scope("sable.pack"):
            v1 = jnp.concatenate([jnp.zeros((1,), val.dtype), val])
            return v1[jnp.asarray(self.tiled.val_gather)].reshape(
                self.tiled.n_tiles, self.tiled.tm, self.tiled.tk
            )

    def __call__(self, val, x):
        return self._fn(val, x)

    def compile(self, val_spec, x_spec) -> "StagedKernel":
        """AOT Stage-2 compile; records the 'inspection' (compile) time the
        paper reports in Tables II/IV."""
        t0 = time.perf_counter()
        self._fn = self._fn.lower(val_spec, x_spec).compile()
        self.compile_time = time.perf_counter() - t0
        return self

    @property
    def inspection_time(self) -> float:
        return self.stage0_time + self.compile_time


# ---------------------------------------------------------------------- #
# Public API + executable cache (compile once / run many)
# ---------------------------------------------------------------------- #
_CACHE: dict[tuple, StagedKernel] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def _cached(kind, vbr, opts, hints, n_cols=None) -> StagedKernel:
    key = (kind, vbrlib.structure_hash(vbr), n_cols, opts.key())
    hit = _CACHE.get(key)
    if hit is not None:
        _CACHE_STATS["hits"] += 1
        return hit
    _CACHE_STATS["misses"] += 1
    kern = StagedKernel(kind, vbr, opts, hints=hints, n_cols=n_cols)
    _CACHE[key] = kern
    return kern


def stage_spmv(
    vbr: vbrlib.VBR,
    opts: StagingOptions = StagingOptions(),
    value_hints: Optional[np.ndarray] = None,
    *,
    mesh=None,
    shards: Optional[int] = None,
    shard_axis: str = "shards",
    model_axis: str = "model",
    shard_strategy: str = "lpt",
    overlap_gather: bool = True,
):
    """Stage a pattern-specialized SpMV kernel.

    With ``mesh=`` (a 1-D or 2-D device mesh, see
    ``launch.mesh.make_staging_mesh``) or ``shards=N``, the block rows are
    partitioned into nnz-balanced shards, each shard is staged for its own
    block-size distribution, and execution runs under ``shard_map`` across
    the mesh (``shards=`` alone: a host-loop reference of the same split).
    Returns a :class:`~repro.core.sharded.ShardedStagedKernel` in that
    case.  ``overlap_gather`` (default on) assembles the output with a
    ``ppermute`` ring inside ``shard_map`` so gather traffic overlaps
    shard compute instead of a trailing all-gather.
    """
    if opts.backend == "dia_hybrid":
        if mesh is not None or shards is not None:
            raise ValueError(
                "backend='dia_hybrid' is unsharded (the diagonal gather "
                "spans the full row range); stage unsharded or pick "
                "another backend for the mesh path"
            )
        from ..kernels.dia_hybrid import stage_dia_hybrid

        return stage_dia_hybrid(vbr, opts=opts)
    if mesh is not None or shards is not None:
        from .sharded import ShardedStagedKernel

        return ShardedStagedKernel(
            "spmv", vbr, opts, num_shards=shards, mesh=mesh,
            shard_axis=shard_axis, model_axis=model_axis,
            strategy=shard_strategy, hints=value_hints,
            overlap_gather=overlap_gather,
        )
    if opts.backend == "autotune":
        from .autotune import autotune_stage

        return autotune_stage(vbr, "spmv", value_hints=value_hints, base_opts=opts)
    hints = vbr.val if (opts.density_threshold > 0 and value_hints is None) else value_hints
    return _cached("spmv", vbr, opts, hints)


def stage_spmm(
    vbr: vbrlib.VBR,
    n_cols: int,
    opts: StagingOptions = StagingOptions(),
    value_hints: Optional[np.ndarray] = None,
    *,
    mesh=None,
    shards: Optional[int] = None,
    shard_axis: str = "shards",
    model_axis: str = "model",
    shard_strategy: str = "lpt",
    overlap_gather: bool = True,
):
    """Stage a pattern-specialized SpMM kernel; ``mesh=``/``shards=`` as in
    :func:`stage_spmv`.  On a 2-D (shards x model) mesh the RHS columns
    are partitioned over the model axis (``n_cols`` must divide evenly)."""
    if opts.backend == "dia_hybrid":
        raise ValueError("backend='dia_hybrid' is SpMV-only")
    if mesh is not None or shards is not None:
        from .sharded import ShardedStagedKernel

        return ShardedStagedKernel(
            "spmm", vbr, opts, num_shards=shards, mesh=mesh,
            shard_axis=shard_axis, model_axis=model_axis,
            strategy=shard_strategy, hints=value_hints,
            n_cols=n_cols, overlap_gather=overlap_gather,
        )
    if opts.backend == "autotune":
        from .autotune import autotune_stage

        return autotune_stage(
            vbr, "spmm", n_cols, value_hints=value_hints, base_opts=opts
        )
    hints = vbr.val if (opts.density_threshold > 0 and value_hints is None) else value_hints
    return _cached("spmm", vbr, opts, hints, n_cols=n_cols)


def stage_block_op(vbr: vbrlib.VBR, user_op: Callable, extra_arrays=("x",)):
    """Extensibility hook (Section IV-A): stage an ARBITRARY user DSL op
    over every block with the generic vectorized backend.

    ``user_op(row_idxs, col_idxs, block_view, *arrays, out)`` is staged per
    block; returns ``fn(val, *arrays, out0) -> out``.
    """
    val_av = ArrayVal("val")
    out_av = ArrayVal("out")
    extra_avs = [ArrayVal(n) for n in extra_arrays]
    progs = []
    for t in vbr.blocks():
        prog = stage_op(
            user_op,
            RepRange(t.row_start, t.row_end),
            RepRange(t.col_start, t.col_end),
            ArrayView(val_av, t.val_offset),
            *extra_avs,
            out_av,
        )
        progs.append(prog)

    @jax.jit
    def fn(val, *args):
        *extras, out0 = args
        env = {"val": val, "out": out0}
        env.update({n: a for n, a in zip(extra_arrays, extras)})
        for prog in progs:
            env = run_vectorized(prog, env)
        return env["out"]

    return fn


def partition_block_rows(vbr: vbrlib.VBR, num_workers: int) -> list[list[int]]:
    """Paper Section IV-D load balancing: group block rows into tasks by
    total block size (greedy longest-processing-time bin packing)."""
    sizes = np.zeros(vbr.num_block_rows, dtype=np.int64)
    for t in vbr.blocks():
        sizes[t.block_row] += t.size
    order = np.argsort(-sizes)
    bins: list[list[int]] = [[] for _ in range(num_workers)]
    loads = np.zeros(num_workers, dtype=np.int64)
    for a in order:
        w = int(np.argmin(loads))
        bins[w].append(int(a))
        loads[w] += int(sizes[a])
    return bins


def clear_cache() -> None:
    import sys

    _CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0)
    # the reblock/dia wrappers keep their own kernel memos keyed the same
    # way — a "fresh process" simulation must drop those too
    for modname, fn in (
        ("repro.core.reblock", "clear_reblock_cache"),
        ("repro.kernels.dia_hybrid", "clear_dia_cache"),
    ):
        mod = sys.modules.get(modname)
        if mod is not None:
            getattr(mod, fn)()


def cache_info() -> dict:
    return dict(_CACHE_STATS, size=len(_CACHE))
