"""Production mesh construction.

Meshes are built by FUNCTIONS (never at module import) so importing this
module cannot touch jax device state before the launcher sets XLA_FLAGS.

Production target: TPU v5e pods, 256 chips each.
  single-pod  (16, 16)    ("data", "model")
  multi-pod   (2, 16, 16) ("pod", "data", "model")  — 512 chips; the pod
              axis crosses the DCN boundary (slower links), which is why
              pipeline/pure-DP parallelism lives there.
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_local_mesh",
    "make_staging_mesh",
    "HW",
]


# TPU v5e hardware constants used by the roofline analysis
HW = {
    "peak_bf16_flops": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link (~49 GB/s)
    "dcn_bw": 6.25e9,  # bytes/s per host cross-pod (50 Gbps)
    "hbm_bytes": 16e9,  # per chip
}


def make_mesh(shape, axes, devices=None):
    """The one mesh factory: every axis is ``AxisType.Auto``, which is what
    ``with_sharding_constraint`` and the activation-sharding context
    (``distributed/ctx.py``) expect.  ``jax.make_mesh`` alone now defaults
    to Explicit axes.  ``devices`` pins the mesh to those devices in order
    (default: all devices, laid out by ``jax.make_mesh``)."""
    from jax.sharding import AxisType, Mesh

    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)
    grid = np.asarray(devices).reshape(tuple(shape))
    return Mesh(grid, tuple(axes), axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_staging_mesh(
    num_shards: int | tuple | None = None,
    axis: str = "shards",
    *,
    model: int | None = None,
    model_axis: str = "model",
):
    """Mesh for sharded staged execution (``stage_spmv(..., mesh=)``).

    1-D (the PR-3 behaviour): ``make_staging_mesh(8)`` — a ``"shards"``
    axis over the first 8 devices.  2-D: ``make_staging_mesh(4, model=2)``
    or ``make_staging_mesh((4, 2))`` — a ``("shards", "model")`` mesh where
    the model axis column-partitions the dense SpMM operand (and composes
    with tensor-parallel layers; see docs/architecture.md).  On CPU, force
    multiple host devices first:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    if isinstance(num_shards, (tuple, list)):
        if model is not None:
            raise ValueError("pass either a (shards, model) tuple or model=")
        num_shards, model = (int(d) for d in num_shards)
    devs = jax.devices()
    if num_shards is not None:
        n = num_shards
    else:  # all devices by default; with model= given, shards fill the rest
        n = len(devs) if model is None else len(devs) // max(model, 1)
    if model is None:
        if n > len(devs):
            raise ValueError(
                f"asked for {n} shards but only {len(devs)} devices"
            )
        return make_mesh((n,), (axis,), devices=devs[:n])
    if n < 1 or n * model > len(devs):
        raise ValueError(
            f"asked for {n}x{model} mesh but only {len(devs)} devices"
        )
    return make_mesh((n, model), (axis, model_axis), devices=devs[: n * model])


def make_local_mesh(axes=("data", "model"), shape=None):
    """Mesh over whatever devices exist (tests/examples)."""
    n = jax.device_count()
    if shape is None:
        if len(axes) == 1:
            shape = (n,)
        else:
            m = 1
            for f in (2, 4, 8):
                if n % f == 0 and f <= n:
                    m = f
            shape = (n // m, m) if len(axes) == 2 else (1, n // m, m)
    assert int(np.prod(shape)) == n, f"{shape} != {n} devices"
    return make_mesh(shape, axes)
