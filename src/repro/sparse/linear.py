"""Block-sparse linear layers: SABLE staged patterns as NN weights.

This is the paper's motivating application (NN inference over pruned
weights with a fixed sparsity pattern — SpReg's setting).  A weight matrix
is stored as uniform (tm, tk) tiles plus a *static* pattern (tile
coordinates).  The pattern is structure — fixed at staging/trace time — so
XLA compiles a specialized program per pattern, exactly the SABLE contract;
the tile values are the trainable parameters.

Compute strategies mirror ``core.staging`` backends:
  * grouped einsum + scatter-add (XLA SPMD-shardable, default), or
  * the Pallas ``bsr_spmm`` kernel (TPU hot path).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "BlockPattern",
    "random_pattern",
    "pack_dense",
    "prune_dense",
    "pattern_hash",
    "sparse_matmul",
    "sparse_matmul_pallas",
    "sparse_matmul_auto",
    "choose_matmul_strategy",
    "warm_matmul_plans",
]


@dataclasses.dataclass(frozen=True)
class BlockPattern:
    """Static block-sparsity pattern of a (d_in, d_out) weight matrix."""

    d_in: int
    d_out: int
    tm: int  # tile rows (input dim)
    tk: int  # tile cols (output dim)
    rows: tuple  # (nt,) tile-row coordinates
    cols: tuple  # (nt,) tile-col coordinates

    @property
    def n_tiles(self) -> int:
        return len(self.rows)

    @property
    def density(self) -> float:
        total = (self.d_in // self.tm) * (self.d_out // self.tk)
        return self.n_tiles / max(total, 1)

    def row_gather(self) -> np.ndarray:  # (nt, tm) input-dim indices
        r = np.asarray(self.rows)[:, None] * self.tm + np.arange(self.tm)[None, :]
        return r.astype(np.int32)

    def col_gather(self) -> np.ndarray:  # (nt, tk) output-dim indices
        c = np.asarray(self.cols)[:, None] * self.tk + np.arange(self.tk)[None, :]
        return c.astype(np.int32)

    def flops_fraction(self) -> float:
        return self.density


def random_pattern(
    d_in: int, d_out: int, tm: int, tk: int, density: float, seed: int = 0
) -> BlockPattern:
    """Random pattern with full row/col coverage (every input tile-row and
    output tile-col touched at least once, so no dead units)."""
    assert d_in % tm == 0 and d_out % tk == 0, "dims must be tile-aligned"
    R, C = d_in // tm, d_out // tk
    rng = np.random.default_rng(seed)
    n = max(int(round(density * R * C)), max(R, C))
    # coverage diagonal first
    diag = [(i % R, i % C) for i in range(max(R, C))]
    chosen = set(diag)
    all_cells = [(r, c) for r in range(R) for c in range(C)]
    rng.shuffle(all_cells)
    for cell in all_cells:
        if len(chosen) >= n:
            break
        chosen.add(cell)
    cells = sorted(chosen)
    rows = tuple(r for r, _ in cells)
    cols = tuple(c for _, c in cells)
    return BlockPattern(d_in, d_out, tm, tk, rows, cols)


def prune_dense(
    w: np.ndarray, tm: int, tk: int, density: float
) -> tuple[BlockPattern, np.ndarray]:
    """Magnitude-based block pruning of a dense matrix -> (pattern, tiles).

    Keeps the top ``density`` fraction of (tm, tk) blocks by Frobenius norm
    — how a real pruning pipeline would produce SABLE patterns.
    """
    d_in, d_out = w.shape
    assert d_in % tm == 0 and d_out % tk == 0
    R, C = d_in // tm, d_out // tk
    blocks = w.reshape(R, tm, C, tk).transpose(0, 2, 1, 3)  # (R, C, tm, tk)
    norms = np.sqrt((blocks**2).sum(axis=(2, 3)))
    n = max(int(round(density * R * C)), 1)
    thresh = np.partition(norms.reshape(-1), -n)[-n]
    keep = norms >= thresh
    rs, cs = np.nonzero(keep)
    order = np.lexsort((cs, rs))
    rs, cs = rs[order], cs[order]
    pattern = BlockPattern(d_in, d_out, tm, tk, tuple(rs.tolist()), tuple(cs.tolist()))
    tiles = blocks[rs, cs]  # (nt, tm, tk)
    return pattern, tiles


def pack_dense(w: jnp.ndarray, pattern: BlockPattern) -> jnp.ndarray:
    """Extract the pattern's tiles from a dense (d_in, d_out) matrix."""
    R = pattern.d_in // pattern.tm
    C = pattern.d_out // pattern.tk
    blocks = w.reshape(R, pattern.tm, C, pattern.tk).transpose(0, 2, 1, 3)
    return blocks[np.asarray(pattern.rows), np.asarray(pattern.cols)]


def _as_block_matrix(tiles: jnp.ndarray, pattern: BlockPattern):
    """View (pattern, tiles) as a fixed-block ``BlockMatrix`` — the static
    patterns of this module are just the slow-changing corner of the
    blocked-CSR-COO format (same row-major slot order, no padding)."""
    from .block_csr import BlockMatrix

    return BlockMatrix.from_pattern(pattern, tiles)


def sparse_matmul(x: jnp.ndarray, tiles: jnp.ndarray, pattern: BlockPattern):
    """y[..., d_out] = x[..., d_in] @ W_sparse — the ``dds`` member of the
    ``kernels.bsr_ops`` op family with the grouped-einsum backend (gather
    input tile-rows, batched tile matmul, scatter-add output cols).
    FLOPs = density * dense FLOPs; grads come from the family's
    ``custom_vjp`` (``d(dds)/d(sparse) = sdd``)."""
    from ..kernels.bsr_ops import dds

    lead = x.shape[:-1]
    y = dds(x.reshape(-1, pattern.d_in), _as_block_matrix(tiles, pattern),
            backend="grouped")
    return y.reshape(*lead, pattern.d_out)


def sparse_matmul_pallas(
    x: jnp.ndarray, tiles: jnp.ndarray, pattern: BlockPattern, interpret=None
):
    """TPU hot path: Pallas bsr_spmm over the pattern (x rows = tokens).

    The kernel computes W^T x^T layout-wise: we feed x^T as the dense
    operand with tile tables transposed so output columns become rows.
    """
    from ..kernels import ops as kops

    lead = x.shape[:-1]
    xt = x.reshape(-1, pattern.d_in).T  # (d_in, T)
    # kernel contracts tile @ x[tile_col_range] over rows => swap roles
    order = np.lexsort((np.asarray(pattern.rows), np.asarray(pattern.cols)))
    row_ids = np.asarray(pattern.cols)[order].astype(np.int32)  # output tiles
    col_ids = np.asarray(pattern.rows)[order].astype(np.int32)  # input tiles
    tiles_t = jnp.transpose(tiles[jnp.asarray(order)], (0, 2, 1))  # (nt, tk, tm)
    # coverage of all output tiles is guaranteed by random_pattern
    yt = kops.bsr_spmm(
        tiles_t,
        jnp.asarray(row_ids),
        jnp.asarray(col_ids),
        xt,
        m_pad=pattern.d_out,
        interpret=interpret,
    )
    return yt.T.reshape(*lead, pattern.d_out)


# ---------------------------------------------------------------------- #
# AD-safe Pallas dispatch: pallas_call has no transpose rule, so training
# through the kernel would raise.  Forward runs the kernel; backward is the
# (differentiable) gather/einsum formulation of the same contraction.
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_matmul_ad(pattern: BlockPattern, x, tiles):
    return sparse_matmul_pallas(x, tiles, pattern)


def _pallas_matmul_fwd(pattern, x, tiles):
    return sparse_matmul_pallas(x, tiles, pattern), (x, tiles)


def _pallas_matmul_bwd(pattern, res, g):
    x, tiles = res
    rg = jnp.asarray(pattern.row_gather())  # (nt, tm)
    cg = jnp.asarray(pattern.col_gather())  # (nt, tk)
    gg = g[..., cg]  # (..., nt, tk)
    dx = (
        jnp.zeros_like(x)
        .at[..., rg]
        .add(jnp.einsum("...nk,nmk->...nm", gg, tiles))
    )
    dtiles = jnp.einsum("...nm,...nk->nmk", x[..., rg], gg)
    return dx, dtiles.astype(tiles.dtype)


_pallas_matmul_ad.defvjp(_pallas_matmul_fwd, _pallas_matmul_bwd)


# ---------------------------------------------------------------------- #
# Plan-driven strategy selection (shares core.cache with the autotuner)
# ---------------------------------------------------------------------- #
def _fixed_block_matmul(x: jnp.ndarray, tiles: jnp.ndarray,
                        pattern: BlockPattern):
    """Inspection-free strategy: route through the fixed-block op family
    (``kernels.bsr_ops.dds``, auto backend — pallas on TPU).  Used when
    the structure-change-rate arbiter decides the pattern churns too fast
    for staging/plan-caching to amortize."""
    from ..kernels.bsr_ops import dds

    lead = x.shape[:-1]
    y = dds(x.reshape(-1, pattern.d_in), _as_block_matrix(tiles, pattern))
    return y.reshape(*lead, pattern.d_out)


def _dia_split(pattern: BlockPattern):
    """Staging-time split of a pattern's tiles into the diagonal band
    (each output tile-col used by at most one band tile, so the diagonal
    half of the product is scatter-free) and the remainder."""
    rows = np.asarray(pattern.rows)
    cols = np.asarray(pattern.cols)
    R = max(pattern.d_in // pattern.tm, 1)
    C = max(pattern.d_out // pattern.tk, 1)
    band = np.abs((cols + 0.5) / C - (rows + 0.5) / R) <= max(1.0 / R, 1.0 / C)
    diag_idx: list[int] = []
    used_cols: set[int] = set()
    for i in np.nonzero(band)[0]:
        if int(cols[i]) not in used_cols:
            used_cols.add(int(cols[i]))
            diag_idx.append(int(i))
    off_idx = sorted(set(range(len(rows))) - set(diag_idx))
    return np.asarray(diag_idx, np.int64), np.asarray(off_idx, np.int64)


def _dia_hybrid_matmul(x: jnp.ndarray, tiles: jnp.ndarray,
                       pattern: BlockPattern):
    """DIA-hybrid strategy (kernels/dia_hybrid.py, NN-path counterpart):
    the diagonal-band tiles place their outputs with a precomputed gather
    (sentinel 0 = untouched col) instead of a scatter-add; only the
    remainder tiles go through the grouped scatter path."""
    lead = x.shape[:-1]
    diag_idx, off_idx = _dia_split(pattern)
    rows = np.asarray(pattern.rows)
    cols = np.asarray(pattern.cols)
    tm, tk = pattern.tm, pattern.tk
    xf = x.reshape(-1, pattern.d_in)
    y = jnp.zeros((xf.shape[0], pattern.d_out), dtype=x.dtype)
    if len(diag_idx):
        rg = rows[diag_idx][:, None] * tm + np.arange(tm)[None, :]
        part = jnp.einsum(
            "btm,tmk->btk", xf[:, jnp.asarray(rg)], tiles[jnp.asarray(diag_idx)]
        )
        place = np.zeros(pattern.d_out, np.int64)  # 0 = the sentinel zero
        for j, t in enumerate(diag_idx):
            c0 = int(cols[t]) * tk
            place[c0 : c0 + tk] = j * tk + np.arange(tk) + 1
        part1 = jnp.concatenate(
            [jnp.zeros((xf.shape[0], 1), part.dtype),
             part.reshape(xf.shape[0], -1)],
            axis=1,
        )
        y = part1[:, jnp.asarray(place)]
    if len(off_idx):
        sub = BlockPattern(
            pattern.d_in, pattern.d_out, tm, tk,
            tuple(int(r) for r in rows[off_idx]),
            tuple(int(c) for c in cols[off_idx]),
        )
        y = y + sparse_matmul(xf, tiles[jnp.asarray(off_idx)], sub)
    return y.reshape(*lead, pattern.d_out)


_MATMUL_IMPLS = {
    "grouped": sparse_matmul,
    "pallas": lambda x, tiles, pattern: _pallas_matmul_ad(pattern, x, tiles),
    "fixed_block": _fixed_block_matmul,
    "dia_hybrid": _dia_hybrid_matmul,
}
# (pattern hash, device) -> strategy name, resolved once per process
# (trace-safe).  The device is part of the key: the on-disk plan_key is
# device-specific, and a process whose default backend flips (cpu<->tpu
# test harnesses) must not replay the other backend's winner.
_STRATEGY_REGISTRY: dict[str, str] = {}

# bump when the hash *inputs* change so stale plan-cache entries keyed by
# the old hash miss instead of aliasing (v2: raw coordinate bytes — the
# v1 repr() of numpy coordinate arrays elided large patterns with "...",
# collapsing distinct >1k-tile patterns onto one key)
_PATTERN_HASH_VERSION = b"blockpattern-v2"


def pattern_hash(pattern: BlockPattern) -> str:
    """Structure hash of a BlockPattern (tile coords are the structure).

    Coordinates are canonicalized to int64 and hashed as raw bytes plus
    their shapes, so tuple- and ndarray-carrying patterns agree and large
    patterns never alias (numpy ``repr`` elision truncated them in v1).
    """
    import hashlib

    rows = np.asarray(pattern.rows, dtype=np.int64)
    cols = np.asarray(pattern.cols, dtype=np.int64)
    h = hashlib.sha256()
    h.update(_PATTERN_HASH_VERSION)
    h.update(
        np.asarray(
            [pattern.d_in, pattern.d_out, pattern.tm, pattern.tk,
             rows.size, cols.size],
            dtype=np.int64,
        ).tobytes()
    )
    h.update(rows.tobytes())
    h.update(cols.tobytes())
    return h.hexdigest()[:16]


def choose_matmul_strategy(
    pattern: BlockPattern,
    batch: int = 8,
    cache=None,
    allow_bench: bool = True,
    warmup: int = 1,
    iters: int = 3,
    shard=None,
    family: str = None,
    mode: str = "measure",
    cost_model=None,
    include_dia: bool = False,
) -> str:
    """Measured (or cached) choice between the grouped-einsum and Pallas
    sparse-matmul strategies for one pattern — the ``sparse.linear``
    counterpart of ``core.autotune``, persisted through the same plan cache
    keyed by ``pattern_hash``.

    ``shard=(shard_id, num_shards)`` keys the plan per shard of a device
    mesh (heterogeneous pools can then record different winners per
    device; see ``core.cache.plan_key``).

    On CPU the Pallas kernel only runs in interpret mode and can never win,
    so the candidate set collapses to ``grouped`` and no benchmark runs.

    With ``family=`` the structure-change-rate arbiter
    (``core.autotune.choose_format``) sees this pattern first: a family
    whose observed structure churns per call gets the inspection-free
    ``fixed_block`` strategy immediately — no benchmark, no registry or
    plan-cache write, since caching per-structure plans for a structure
    that never repeats only pollutes the cache.  Slow-changing families
    fall through to the staged (measured/cached) path below.

    ``mode="predict"`` consults the learned cost model over the ``linear``
    plan corpus (``core/cost_model.py``) before benchmarking: a confident
    prediction records a ``source="predicted"`` plan with ZERO
    micro-benchmarks (this is how ``warm_matmul_plans`` warms a thousand
    patterns in seconds); an uncertain one falls back to measurement.
    ``cost_model=`` pins a pre-loaded model so batch warmers fit once.

    ``include_dia=True`` opts into structure detection
    (``core.inspect.detect_pattern``): a pattern whose tiles sit densely on
    the diagonal band gains the ``dia_hybrid`` candidate (scatter-free
    diagonal placement, see ``_dia_hybrid_matmul``).  It is opt-in because
    it widens the candidate space — plans are therefore keyed with the
    ``rb`` plan-key segment (and an ``@rb`` registry suffix) so they never
    alias base-space plans, and because ``random_pattern`` seeds a coverage
    diagonal that would otherwise trip detection on patterns that are not
    meaningfully diagonal.  Note the pattern itself is never re-tiled:
    ``BlockPattern`` tiles are the parameter layout of a live model, so
    unlike VBR reblocking (``core.reblock``) only the *compute schedule*
    changes.  The ``family=`` churn check still runs first — churny
    patterns never pay for detection.
    """
    if mode not in ("measure", "predict"):
        raise ValueError(f"unknown strategy mode {mode!r}")
    from ..core import cache as cachelib
    from ..core.staging import StagingOptions

    phash = pattern_hash(pattern)
    if family is not None:
        from ..core.autotune import choose_format

        if choose_format(family, phash) == "fixed_block":
            return "fixed_block"
    device = jax.default_backend()
    reg_key = f"{phash}@{device}" if shard is None else (
        f"{phash}@{device}@s{shard[0]}of{shard[1]}"
    )
    if include_dia:
        reg_key += "@rb"  # extended candidate space: never alias base plans
    found = _STRATEGY_REGISTRY.get(reg_key)
    if found is not None:
        return found
    key = cachelib.plan_key(
        "linear", phash, device,
        shard_id=None if shard is None else shard[0],
        num_shards=None if shard is None else shard[1],
        reblock=include_dia,
    )
    store = cache if cache is not None else cachelib.default_cache()
    plan = store.load_plan(key)
    if plan is not None:
        _STRATEGY_REGISTRY[reg_key] = plan.options.backend
        return plan.options.backend

    candidates = ["grouped"] + (["pallas"] if device == "tpu" else [])
    struct_meta: dict = {}
    if include_dia:
        from ..core.inspect import detect_pattern

        info = detect_pattern(pattern)
        struct_meta = {
            "structure_class": info.structure_class,
            "bandwidth_frac": info.bandwidth_frac,
            "diag_occupancy": info.diag_occupancy,
        }
        if info.wants_dia:
            candidates.append("dia_hybrid")

    if mode == "predict" and len(candidates) > 1:
        from ..core import cost_model as cmlib

        model = (
            cost_model
            if cost_model is not None
            else cmlib.load_or_fit(store, device, "linear")
        )
        if model is not None:
            feats = cmlib.pattern_features(pattern)
            ok, _why = model.confident(feats, candidates)
            if ok:
                preds = model.predict(feats, candidates)
                best = min(preds, key=preds.get)
                plan = cachelib.TuningPlan(
                    kind="linear",
                    structure_hash=phash,
                    options=StagingOptions(
                        backend=best, tile=(pattern.tm, pattern.tk)
                    ),
                    device=device,
                    timings=preds,  # estimates, NOT measurements
                    meta={
                        "d_in": pattern.d_in,
                        "d_out": pattern.d_out,
                        "tm": pattern.tm,
                        "tk": pattern.tk,
                        "n_tiles": pattern.n_tiles,
                        "density": pattern.density,
                        **struct_meta,
                    },
                    source="predicted",
                )
                store.store_plan(key, plan)
                _STRATEGY_REGISTRY[reg_key] = best
                cmlib._STATS["plans_predicted"] += 1
                return best
        cmlib._STATS["predict_fallbacks"] += 1

    timings: dict[str, float] = {}
    if len(candidates) > 1 and allow_bench:
        from ..core.autotune import measure, record_candidate_failure

        rng = np.random.default_rng(0)
        x = jnp.asarray(
            rng.standard_normal((batch, pattern.d_in)).astype(np.float32)
        )
        tiles = jnp.asarray(
            rng.standard_normal(
                (pattern.n_tiles, pattern.tm, pattern.tk)
            ).astype(np.float32)
        )
        for name in candidates:
            fn = jax.jit(lambda x, t, _f=_MATMUL_IMPLS[name]: _f(x, t, pattern))
            try:
                timings[name] = measure(fn, x, tiles, warmup=warmup, iters=iters)
            except Exception as e:
                record_candidate_failure(name, e)
        best = min(timings, key=timings.get) if timings else "grouped"
        source = "measured" if timings else "heuristic"
    else:
        best = candidates[-1] if not allow_bench else candidates[0]
        source = "heuristic"

    plan = cachelib.TuningPlan(
        kind="linear",
        structure_hash=phash,
        options=StagingOptions(backend=best, tile=(pattern.tm, pattern.tk)),
        device=device,
        timings=timings,
        meta={
            "d_in": pattern.d_in,
            "d_out": pattern.d_out,
            "tm": pattern.tm,
            "tk": pattern.tk,
            "n_tiles": pattern.n_tiles,
            "density": pattern.density,
            **struct_meta,
            **({} if shard is None else
               {"shard_id": shard[0], "num_shards": shard[1]}),
        },
        source=source,
    )
    # a mid-trace heuristic fallback is provisional: keep it out of the
    # persistent cache so a later warm_matmul_plans() can still measure
    if source == "measured" or len(candidates) == 1:
        store.store_plan(key, plan)
        _STRATEGY_REGISTRY[reg_key] = best
    return best


def _seed_shard_strategy(pattern: BlockPattern, shard, strategy: str,
                         cache=None) -> str:
    """Record ``strategy`` under a per-shard plan key WITHOUT benchmarking
    (the device measured the full pattern once; a shard sees the same
    pattern, so the winner is inherited).  A plan already stored under the
    shard key — e.g. measured on that specific device of a heterogeneous
    pool — wins over the inherited default."""
    from ..core import cache as cachelib
    from ..core.staging import StagingOptions

    phash = pattern_hash(pattern)
    device = jax.default_backend()
    reg_key = f"{phash}@{device}@s{shard[0]}of{shard[1]}"
    found = _STRATEGY_REGISTRY.get(reg_key)
    if found is not None:
        return found
    key = cachelib.plan_key("linear", phash, device,
                            shard_id=shard[0], num_shards=shard[1])
    store = cache if cache is not None else cachelib.default_cache()
    plan = store.load_plan(key)
    if plan is None:
        plan = cachelib.TuningPlan(
            kind="linear",
            structure_hash=phash,
            options=StagingOptions(backend=strategy,
                                   tile=(pattern.tm, pattern.tk)),
            device=device,
            meta={"shard_id": shard[0], "num_shards": shard[1]},
            source="inherited",
        )
        store.store_plan(key, plan)
    _STRATEGY_REGISTRY[reg_key] = plan.options.backend
    return plan.options.backend


def warm_matmul_plans(patterns, batch: int = 8, cache=None, mesh=None,
                      shard_axis: str = "shards", mode: str = "measure",
                      include_dia: bool = False) -> dict:
    """Resolve strategies for many patterns ahead of tracing (server
    startup hook — e.g. ``ServeEngine``).  Returns {hash: strategy}.

    With ``mesh=`` the per-shard plan keys for the mesh's shard axis are
    resolved too (``<hash>@sIofN``): the measured winner is benchmarked
    ONCE per pattern and inherited by every shard (no per-shard
    re-benchmarks); a per-shard plan already on disk overrides it.  2-D
    (shards x model) staging meshes warm the same per-shard keys; a mesh
    with no shard axis at all (e.g. a pure ("data", "model") production
    mesh) warms the base plans only.

    ``mode="predict"`` loads/fits the cost model ONCE and resolves every
    cold pattern by prediction where the model is confident — this is the
    thousand-structure warm path: seconds of closed-form ranking instead
    of minutes of per-pattern micro-benchmarks, with per-pattern fallback
    to measurement for out-of-corpus or too-close calls."""
    out = {}
    shard_ids = []
    if mesh is not None:
        from ..core.sharded import resolve_shard_axis

        try:
            axis = resolve_shard_axis(mesh, shard_axis)
        except ValueError:
            axis = None  # no shard axis (e.g. TP-only mesh): base plans only
        if axis is not None:
            shard_ids = list(range(int(mesh.shape[axis])))
    model = None
    if mode == "predict":
        import jax as _jax

        from ..core import cache as cachelib
        from ..core import cost_model as cmlib

        store = cache if cache is not None else cachelib.default_cache()
        model = cmlib.load_or_fit(store, _jax.default_backend(), "linear")
    for p in patterns:
        base = choose_matmul_strategy(
            p, batch=batch, cache=cache, mode=mode, cost_model=model,
            include_dia=include_dia,
        )
        out[pattern_hash(p)] = base
        for i in shard_ids:
            shard = (i, len(shard_ids))
            out[f"{pattern_hash(p)}@s{i}of{len(shard_ids)}"] = (
                _seed_shard_strategy(p, shard, base, cache=cache)
            )
    return out


def sparse_matmul_auto(x: jnp.ndarray, tiles: jnp.ndarray,
                       pattern: BlockPattern, shard=None, mesh=None,
                       out_model: bool = False, family: str = None):
    """Plan-dispatched sparse matmul.  Inside a jit trace an unresolved
    pattern falls back to the device heuristic WITHOUT benchmarking (a
    micro-benchmark mid-trace would compile-thrash); call
    ``warm_matmul_plans`` first to get measured choices under jit.

    ``out_model=True`` marks the output's last dim as tensor-parallel:
    with an explicit ``mesh=`` (1-D or 2-D staging mesh) the constraint
    resolves against that mesh's model axis; without one it goes through
    ``distributed.ctx.constrain`` placeholders, so the same call composes
    with whatever ``activation_sharding`` context the launcher traced
    under (and is a no-op outside any context).
    """
    tracing = isinstance(x, jax.core.Tracer)
    strategy = choose_matmul_strategy(pattern, allow_bench=not tracing,
                                      shard=shard, family=family)
    y = _MATMUL_IMPLS[strategy](x, tiles, pattern)
    if out_model:
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..core.sharded import resolve_model_axis

            maxis = resolve_model_axis(mesh)
            if maxis is not None:
                y = jax.lax.with_sharding_constraint(
                    y,
                    NamedSharding(mesh, P(*([None] * (y.ndim - 1)), maxis)),
                )
        else:
            from ..distributed.ctx import MODEL, constrain

            y = constrain(y, *([None] * (y.ndim - 1) + [MODEL]))
    return y
