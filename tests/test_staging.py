"""Staged kernels: every backend vs the densify oracle; caching; hybrid."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # keep deterministic cases running without hypothesis
    from _hypothesis_stub import given, settings, st

from repro.core import vbr as vbrlib
from repro.core.staging import (
    StagingOptions,
    cache_info,
    clear_cache,
    partition_block_rows,
    stage_block_op,
    stage_spmm,
    stage_spmv,
)
from repro.core.dsl import loopgen

BACKENDS = ["unrolled", "grouped", "gather", "pallas"]


def _mk(seed=0, rows=67, cols=53, rs=6, cs=5, nb=14, sp=0.25, uniform=False):
    return vbrlib.synthesize(rows, cols, rs, cs, nb, sp, uniform, seed)


@pytest.mark.parametrize("backend", BACKENDS)
def test_spmv_backends_vs_oracle(backend):
    v = _mk()
    x = np.random.default_rng(0).standard_normal(v.shape[1]).astype(np.float32)
    ref = v.to_dense() @ x
    k = stage_spmv(v, StagingOptions(backend=backend, tile=(8, 16), interpret=True))
    y = np.asarray(k(jnp.asarray(v.val), jnp.asarray(x)))
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_spmm_backends_vs_oracle(backend):
    v = _mk(seed=1)
    X = np.random.default_rng(1).standard_normal((v.shape[1], 24)).astype(np.float32)
    ref = v.to_dense() @ X
    k = stage_spmm(
        v, 24, StagingOptions(backend=backend, tile=(8, 16), spmm_bn=8, interpret=True)
    )
    y = np.asarray(k(jnp.asarray(v.val), jnp.asarray(X)))
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 1000),
    rows=st.integers(8, 80),
    cols=st.integers(8, 80),
    rs=st.integers(1, 6),
    cs=st.integers(1, 6),
    sp=st.floats(0.0, 0.8),
    backend=st.sampled_from(["unrolled", "grouped"]),
)
def test_spmv_property(seed, rows, cols, rs, cs, sp, backend):
    v = vbrlib.synthesize(rows, cols, rs, cs, max(1, rs * cs // 2), sp, False, seed)
    x = np.random.default_rng(seed).standard_normal(cols).astype(np.float32)
    k = stage_spmv(v, StagingOptions(backend=backend))
    y = np.asarray(k(jnp.asarray(v.val), jnp.asarray(x)))
    np.testing.assert_allclose(y, v.to_dense() @ x, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_spmv_dtypes(dtype):
    v = _mk(seed=2)
    x32 = np.random.default_rng(2).standard_normal(v.shape[1]).astype(np.float32)
    ref = v.to_dense() @ x32
    k = stage_spmv(v, StagingOptions(backend="grouped", dtype=jnp.dtype(dtype)))
    y = np.asarray(
        k(jnp.asarray(v.val), jnp.asarray(x32)), dtype=np.float32
    )
    tol = 1e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(y, ref, rtol=tol, atol=tol)


def test_density_threshold_hybrid():
    """Listing 3: very sparse blocks go through the unrolled COO tail."""
    v = _mk(seed=3, sp=0.9, nb=20)
    x = np.random.default_rng(3).standard_normal(v.shape[1]).astype(np.float32)
    k = stage_spmv(v, StagingOptions(backend="grouped", density_threshold=0.5))
    assert k.coo is not None  # some blocks routed to COO
    assert len(k.descs) < 20  # and fewer dense blocks remain
    y = np.asarray(k(jnp.asarray(v.val), jnp.asarray(x)))
    np.testing.assert_allclose(y, v.to_dense() @ x, rtol=2e-4, atol=2e-4)


def test_executable_cache_same_pattern():
    clear_cache()
    v = _mk(seed=4)
    k1 = stage_spmv(v, StagingOptions(backend="grouped"))
    # same structure, different values => cache hit (compile once/run many)
    v2 = vbrlib.VBR(**{**v.__dict__})
    v2.val = v.val * 2.0
    k2 = stage_spmv(v2, StagingOptions(backend="grouped"))
    assert k1 is k2
    info = cache_info()
    assert info["hits"] == 1 and info["misses"] == 1
    x = np.ones(v.shape[1], np.float32)
    y1 = np.asarray(k1(jnp.asarray(v.val), jnp.asarray(x)))
    y2 = np.asarray(k2(jnp.asarray(v2.val), jnp.asarray(x)))
    np.testing.assert_allclose(y2, 2 * y1, rtol=1e-5)


def test_prepack_amortization():
    v = _mk(seed=5)
    x = np.random.default_rng(5).standard_normal(v.shape[1]).astype(np.float32)
    k = stage_spmv(
        v, StagingOptions(backend="pallas", tile=(8, 16), interpret=True, prepack=True)
    )
    tiles = k.pack(jnp.asarray(v.val))
    y = np.asarray(k(tiles, jnp.asarray(x)))
    np.testing.assert_allclose(y, v.to_dense() @ x, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kind,backend", [("spmv", "grouped"), ("spmm", "grouped"),
                                          ("spmv", "pallas"), ("spmv", "unrolled")])
def test_staged_program_names_its_phases(kind, backend):
    """The staged program is named ``sable_<kind>_<backend>`` and its value
    packing and multiply carry the ``sable.pack`` / ``sable.multiply``
    scopes, which reach the device ops' ``op_name``."""
    v = _mk(seed=6)
    opts = StagingOptions(backend=backend, tile=(8, 16), interpret=True)
    if kind == "spmv":
        k, x = stage_spmv(v, opts), jnp.ones(v.shape[1], jnp.float32)
    else:
        k, x = stage_spmm(v, 4, opts), jnp.ones((v.shape[1], 4), jnp.float32)
    lowered = k._fn.lower(jnp.asarray(v.val), x)
    text = lowered.as_text(debug_info=True)
    assert "sable.pack" in text and "sable.multiply" in text
    hlo = lowered.compile().as_text()
    assert hlo.startswith(f"HloModule jit_sable_{kind}_{backend}")
    assert 'op_name="jit(sable_' in hlo and "/sable.pack/" in hlo


def test_prepacked_tiles_carry_the_pack_scope():
    v = _mk(seed=7)
    k = stage_spmv(v, StagingOptions(backend="pallas", tile=(8, 16), interpret=True,
                                     prepack=True))
    text = jax.jit(k.pack).lower(jnp.asarray(v.val)).as_text(debug_info=True)
    assert "sable.pack" in text


def test_partition_block_rows_balance():
    """Paper IV-D: greedy grouping balances nnz-block load."""
    v = _mk(seed=6, rows=200, cols=200, rs=20, cs=10, nb=80)
    bins = partition_block_rows(v, 4)
    sizes = np.zeros(v.num_block_rows, dtype=np.int64)
    for t in v.blocks():
        sizes[t.block_row] += t.size
    loads = sorted(sum(int(sizes[a]) for a in b) for b in bins)
    assert loads[-1] <= 2 * max(loads[0], 1) + int(sizes.max())
    assert sorted(a for b in bins for a in b) == sorted(
        set(a for b in bins for a in b)
    )


def test_stage_block_op_custom():
    """Extensibility: arbitrary user op staged over all blocks."""
    v = _mk(seed=7)

    def scale_rowsum(r1, r2, blk, x, out):
        def body(i, j):
            out[i] += blk[(j - r2.start) * len(r1) + (i - r1.start)] * x[j]

        loopgen(r1, lambda i: loopgen(r2, lambda j: body(i, j)))

    fn = stage_block_op(v, scale_rowsum, extra_arrays=("x",))
    x = np.random.default_rng(7).standard_normal(v.shape[1]).astype(np.float32)
    out = fn(jnp.asarray(v.val), jnp.asarray(x), jnp.zeros(v.shape[0], jnp.float32))
    np.testing.assert_allclose(np.asarray(out), v.to_dense() @ x, rtol=2e-4, atol=2e-4)


def test_inspection_time_recorded():
    clear_cache()
    v = _mk(seed=8)
    k = stage_spmv(v, StagingOptions(backend="grouped"))
    k.compile(
        jax.ShapeDtypeStruct(v.val.shape, jnp.float32),
        jax.ShapeDtypeStruct((v.shape[1],), jnp.float32),
    )
    assert k.stage0_time > 0 and k.compile_time > 0
    assert k.inspection_time == k.stage0_time + k.compile_time
