"""Sharded staged execution: per-shard staged kernels over a device mesh.

The paper's parallel results split staged block work across workers; this
module is the multi-device version of that split for JAX.  A
:class:`~repro.distributed.partition.ShardPlan` cuts the VBR block rows
into nnz-balanced shards, each shard is staged as its OWN specialized
kernel (so a shard only instantiates kernels for its local block-size
distribution — shard-local staging), and execution runs either:

  * ``shard_map`` SPMD path (``mesh=`` given): one program over the
    ``"shards"`` mesh axis; each device selects its shard's specialized
    sub-program by ``lax.axis_index`` (``lax.switch`` over the staged
    branches).  Values/outputs carry explicit sharding constraints, so the
    SPMD partitioner never has to guess a layout (no involuntary
    rematerialization of the gathered tiles).
  * host loop (no mesh): the per-shard kernels run sequentially and
    scatter into the global output — the reference semantics used by the
    equivalence tests.

2-D (shards x model) meshes: when the mesh also carries a ``"model"``
axis, the dense SpMM operand is column-partitioned over it — device
``(i, j)`` computes shard ``i``'s rows for the ``j``-th column slice, so
the staged sparse kernels compose with tensor-parallel models (the RHS
arrives already model-sharded from a TP layer and the output stays
model-sharded).  Each shard then stages for its LOCAL column count and
its tuning plan is keyed by ``model_cols`` on top of the shard id.

Gather/compute overlap: by default (``overlap_gather=True``) the y-gather
over the shard axis runs as a ``ppermute`` ring INSIDE ``shard_map``
instead of a trailing XLA all-gather.  A trailing all-gather is a barrier
— every device waits for the slowest shard before any result bytes move.
In the ring, a shard that finishes early starts forwarding its output
tile immediately, so gather traffic overlaps with the still-running
shards' compute (XLA lowers the ring steps to async
collective-permute-start/done pairs).

Per-shard tuning plans are persisted keyed by
``(parent structure_hash, device, shard_id[, model_cols])`` via
``core.cache.plan_key`` (``backend='autotune'``), so a restarted server
stages every shard with zero re-benchmarks.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import staging as staginglib
from . import vbr as vbrlib
from .cache import default_cache, plan_key
from .staging import StagingOptions

__all__ = ["ShardedStagedKernel", "resolve_shard_axis", "resolve_model_axis"]


def resolve_shard_axis(mesh, shard_axis: str = "shards") -> str:
    """Pick the mesh axis shards live on: ``shard_axis`` when present, the
    sole axis of a 1-D mesh otherwise."""
    if shard_axis in mesh.axis_names:
        return shard_axis
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"mesh {mesh.axis_names} has no {shard_axis!r} axis; build one with "
        "launch.mesh.make_staging_mesh or pass shard_axis="
    )


def resolve_model_axis(mesh, model_axis: str = "model") -> Optional[str]:
    """The mesh axis the dense operand's columns are partitioned over, or
    None when the mesh has no such axis (pure 1-D sharded staging)."""
    return model_axis if model_axis in mesh.axis_names else None


def _shard_options(
    kind: str,
    parent_hash: str,
    shard,
    base_opts: StagingOptions,
    n_cols,
    cache,
    model_cols=None,
) -> StagingOptions:
    """Resolve the staging options for ONE shard.  'autotune' tunes (or
    loads) a per-shard plan keyed by the parent hash + shard id (+ the
    local column count on a 2-D mesh)."""
    if base_opts.backend != "autotune":
        return base_opts
    from .autotune import autotune

    device = jax.default_backend()
    key = plan_key(
        kind,
        parent_hash,
        device,
        n_cols,
        shard_id=shard.shard_id,
        num_shards=shard.num_shards,
        model_cols=model_cols,
    )
    store = cache if cache is not None else default_cache()
    plan = store.load_plan(key)
    if plan is None:
        # tunes on the shard-local structure (also cached under the shard's
        # own sub-structure hash — two matrices sharing a shard pattern
        # share the plan)
        tune_cols = model_cols if model_cols is not None else n_cols
        plan = autotune(shard.vbr, kind, tune_cols, cache=store)
        plan = dataclasses.replace(
            plan,
            meta={
                **plan.meta,
                "parent_structure_hash": parent_hash,
                "shard_id": shard.shard_id,
                "num_shards": shard.num_shards,
                **({} if model_cols is None else {"model_cols": model_cols}),
            },
        )
        store.store_plan(key, plan)
    return dataclasses.replace(
        plan.options, dtype=base_opts.dtype, interpret=base_opts.interpret
    )


class ShardedStagedKernel:
    """Sharded counterpart of :class:`~repro.core.staging.StagedKernel`:
    ``fn(val, x) -> y`` where ``val`` is the GLOBAL value array and ``y``
    the global output; the block-row split (and, on a 2-D mesh, the model
    column split) is internal."""

    def __init__(
        self,
        kind: str,
        vbr: vbrlib.VBR,
        opts: StagingOptions = StagingOptions(),
        *,
        num_shards: Optional[int] = None,
        mesh=None,
        shard_axis: str = "shards",
        model_axis: str = "model",
        strategy: str = "lpt",
        n_cols: Optional[int] = None,
        hints: Optional[np.ndarray] = None,
        cache=None,
        use_cached_plan: bool = True,
        overlap_gather: bool = True,
    ):
        from ..distributed.partition import (
            load_shard_plan,
            make_shard_plan,
            save_shard_plan,
        )

        t0 = time.perf_counter()
        self.model_axis = None
        self.model_size = 1
        if mesh is not None:
            self.axis = resolve_shard_axis(mesh, shard_axis)
            self.model_axis = resolve_model_axis(mesh, model_axis)
            if self.model_axis == self.axis:
                self.model_axis = None
            if self.model_axis is not None:
                self.model_size = int(mesh.shape[self.model_axis])
            mesh_n = int(mesh.shape[self.axis])
            if num_shards is None:
                num_shards = mesh_n
            elif num_shards != mesh_n:
                raise ValueError(
                    f"shards={num_shards} != mesh axis {self.axis!r} size {mesh_n}"
                )
        elif num_shards is None:
            raise ValueError("need mesh= or shards=")
        else:
            self.axis = shard_axis
        if opts.prepack:
            raise ValueError("prepack is not supported for sharded staging")

        # 2-D mesh: the model axis column-partitions the SpMM RHS, so each
        # shard stages (and autotunes) for its LOCAL column count
        self.local_cols = n_cols
        if kind == "spmm" and self.model_size > 1:
            if n_cols is None or n_cols % self.model_size != 0:
                raise ValueError(
                    f"n_cols={n_cols} must divide evenly over the "
                    f"{self.model_axis!r} axis (size {self.model_size})"
                )
            self.local_cols = n_cols // self.model_size

        self.kind = kind
        self.opts = opts
        self.mesh = mesh
        self.overlap_gather = overlap_gather
        self.m, self.k = vbr.shape
        self.n_cols = n_cols
        self.structure_hash = vbrlib.structure_hash(vbr)
        self.plan = None
        if use_cached_plan:
            self.plan = load_shard_plan(vbr, num_shards, strategy, cache=cache)
        if self.plan is None:
            self.plan = make_shard_plan(vbr, num_shards, strategy)
            if use_cached_plan:
                save_shard_plan(self.plan, cache=cache)
        self.num_shards = num_shards

        # shard-local staging: each shard compiles kernels only for its own
        # block-size distribution (the in-memory executable cache dedups
        # shards that happen to share a pattern)
        model_cols = self.local_cols if self.model_size > 1 else None
        self.kernels = []
        for s in self.plan.shards:
            s_opts = _shard_options(
                kind, self.structure_hash, s, opts, n_cols, cache,
                model_cols=model_cols,
            )
            s_hints = hints[s.val_index] if hints is not None else None
            if s_opts.density_threshold > 0 and s_hints is None:
                s_hints = s.vbr.val
            self.kernels.append(
                staginglib._cached(
                    kind, s.vbr, s_opts, s_hints, n_cols=self.local_cols
                )
            )
        self.num_blocks = sum(s.vbr.num_blocks for s in self.plan.shards)

        self._build_maps()
        self._fn = jax.jit(
            self._build_spmd() if mesh is not None else self._build_host()
        )
        self.stage0_time = time.perf_counter() - t0
        self.compile_time = 0.0

    # ------------------------------------------------------------------ #
    def _build_maps(self) -> None:
        shards = self.plan.shards
        D = self.num_shards
        self.max_nnz = max((s.nnz for s in shards), default=0)
        self.max_rows = max((s.local_m for s in shards), default=0)
        # (D, max_nnz) gather map into 1-shifted global val (0 = pad zero)
        vg = np.zeros((D, max(self.max_nnz, 1)), dtype=np.int64)
        for s in shards:
            vg[s.shard_id, : s.nnz] = s.val_index + 1
        self.val_gather = vg.astype(np.int32)
        # (m,) gather from 1-shifted flattened padded outputs (0 = zero)
        ys = np.zeros((self.m,), dtype=np.int64)
        for s in shards:
            local = np.arange(s.local_m, dtype=np.int64)
            ys[s.row_index] = s.shard_id * max(self.max_rows, 1) + local + 1
        self.y_src = ys.astype(np.int32)

    # ------------------------------------------------------------------ #
    def _build_host(self):
        shards, kernels, kind = self.plan.shards, self.kernels, self.kind

        def fn(val, x):
            y = jnp.zeros(self._out_shape(x), dtype=x.dtype)
            for s, kern in zip(shards, kernels):
                if s.nnz == 0 and s.vbr.num_blocks == 0:
                    continue
                ys = kern(val[jnp.asarray(s.val_index)], x)
                y = y.at[jnp.asarray(s.row_index)].set(ys.astype(x.dtype))
            return y

        del kind
        return fn

    def _build_spmd(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis = self.mesh, self.axis
        shards, kernels = self.plan.shards, self.kernels
        kind = self.kind
        D, max_rows = self.num_shards, self.max_rows
        val_gather = self.val_gather
        y_src = self.y_src
        # model axis column split applies to the SpMM RHS only (SpMV's x
        # is a vector — it replicates across the model axis)
        col_axis = (
            self.model_axis
            if (kind == "spmm" and self.model_size > 1)
            else None
        )
        overlap = self.overlap_gather and D > 1

        def branch_for(s, kern):
            def br(vs, x):
                v = vs[0, : max(s.nnz, 1)][: s.nnz]
                ys = kern(v, x).astype(x.dtype)
                pad = max_rows - s.local_m
                if pad:
                    ys = jnp.concatenate(
                        [ys, jnp.zeros((pad,) + ys.shape[1:], ys.dtype)]
                    )
                return ys

            return br

        branches = [branch_for(s, k) for s, k in zip(shards, kernels)]
        ring = [(j, (j + 1) % D) for j in range(D)]

        def local(vs, x):
            i = jax.lax.axis_index(axis)
            ys = jax.lax.switch(i, branches, vs, x)  # (max_rows[, nloc])
            if not overlap:
                return ys[None]
            # ppermute ring all-gather over the shard axis: shard i's tile
            # reaches every device in D-1 hops.  A shard that finishes
            # early forwards immediately, so its gather traffic overlaps
            # with slower shards' compute — no barrier all-gather.
            buf = jnp.zeros((D,) + ys.shape, ys.dtype).at[i].set(ys)
            cur = ys
            for t in range(1, D):
                cur = jax.lax.ppermute(cur, axis, ring)
                buf = buf.at[(i - t) % D].set(cur)
            # reassemble the full output locally (pure data movement —
            # row spans are disjoint, there is no cross-shard reduction)
            flat = buf.reshape((D * max_rows,) + ys.shape[1:])
            z = jnp.zeros((1,) + flat.shape[1:], flat.dtype)
            return jnp.concatenate([z, flat])[jnp.asarray(y_src)]

        x_parts = (None,) if kind == "spmv" else (None, col_axis)
        if overlap:
            out_specs = P(None, *x_parts[1:])  # assembled in-ring
        else:
            out_specs = P(axis, *x_parts)
        mapped = jax.shard_map(
            local, mesh=mesh, in_specs=(P(axis, None), P(*x_parts)),
            out_specs=out_specs, check_vma=False,
        )

        def fn(val, x):
            # explicit layouts end-to-end: the tile gather lands directly
            # in the (shards, nnz) sharded layout and x arrives replicated
            # over shards (and column-split over the model axis on a 2-D
            # mesh) — nothing is left for the partitioner to rematerialize.
            val1 = jnp.concatenate([jnp.zeros((1,), val.dtype), val])
            vp = val1[jnp.asarray(val_gather)]
            vp = jax.lax.with_sharding_constraint(
                vp, NamedSharding(mesh, P(axis, None))
            )
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*x_parts))
            )
            if overlap:
                y = mapped(vp, x)  # (m[, n]) — gathered inside the ring
            else:
                yp = mapped(vp, x)  # (D, max_rows[, n])
                # replicate BEFORE the reshape: reshaping across the
                # sharded dim on a 2-D mesh trips an XLA SPMD partitioner
                # miscompile (output scaled by model_size^2 — same family
                # as the PR-3 involuntary-remat bugs); an explicit
                # all-gather here keeps the partitioner out of the
                # reshape/gather chain entirely
                yp = jax.lax.with_sharding_constraint(
                    yp, NamedSharding(mesh, P(None, None, *x_parts[1:]))
                )
                flat = yp.reshape((D * max_rows,) + yp.shape[2:])
                z = jnp.zeros((1,) + flat.shape[1:], flat.dtype)
                y = jnp.concatenate([z, flat])[jnp.asarray(y_src)]
            # rows replicated; SpMM columns stay model-sharded so the
            # output feeds a tensor-parallel consumer without a reshard
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P(None, *x_parts[1:]))
            )

        return fn

    # ------------------------------------------------------------------ #
    def _out_shape(self, x):
        return (self.m,) if self.kind == "spmv" else (self.m, x.shape[1])

    def __call__(self, val, x):
        return self._fn(val, x)

    def compile(self, val_spec, x_spec) -> "ShardedStagedKernel":
        t0 = time.perf_counter()
        self._fn = self._fn.lower(val_spec, x_spec).compile()
        self.compile_time = time.perf_counter() - t0
        return self

    @property
    def inspection_time(self) -> float:
        return self.stage0_time + self.compile_time

    def imbalance(self) -> float:
        return self.plan.imbalance()
