"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at real widths and compiles it for
a chip that is described, not attached, so Mosaic refuses here what it
would refuse on the chip (block shapes not aligned to the (8, 128) tiling,
too much VMEM).  ``interpret=False`` is passed explicitly because the
default backend in the tests is the CPU.  The topology is described inside
a module fixture (only one process may load the TPU library), and the
tests skip when it cannot be described.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shardings, *shapes):
    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=shardings)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# the paper's 10k x 10k matrices staged with (8, 128) tiles: 100-row blocks
# pad to 104 rows and 100-column blocks to 128 columns
M_PAD, K_PAD, N_TILES = 10_400, 12_800, 6_500


def test_bsr_spmv_compiles_at_paper_size(one_chip):
    from repro.kernels.bsr_spmv import bsr_spmv_pallas

    fn = functools.partial(bsr_spmv_pallas, m_pad=M_PAD, interpret=False)
    _compile(
        fn, one_chip,
        ((N_TILES, 8, 128), jnp.float32),
        ((N_TILES,), jnp.int32),
        ((N_TILES,), jnp.int32),
        ((K_PAD,), jnp.float32),
    )


def test_bsr_spmm_f32_compiles_at_paper_size(one_chip):
    from repro.kernels.bsr_spmm import bsr_spmm_pallas

    fn = functools.partial(bsr_spmm_pallas, m_pad=M_PAD, bn=128, interpret=False)
    _compile(
        fn, one_chip,
        ((N_TILES, 8, 128), jnp.float32),
        ((N_TILES,), jnp.int32),
        ((N_TILES,), jnp.int32),
        ((K_PAD, 128), jnp.float32),
    )


@pytest.mark.parametrize("d_in,d_out", [(4096, 14336), (14336, 4096)])
def test_bsr_spmm_bf16_compiles_at_llama3_8b_ffn_widths(one_chip, d_in, d_out):
    """The sparse-FFN ``pallas`` strategy at llama3-8b-sable's widths: 128 x
    128 bf16 tiles at density 0.25, 8 tokens (``sparse_matmul_pallas``)."""
    from repro.kernels.bsr_spmm import bsr_spmm_pallas

    n_tiles = (d_in // 128) * (d_out // 128) // 4
    fn = functools.partial(bsr_spmm_pallas, m_pad=d_out, bn=8, interpret=False)
    _compile(
        fn, one_chip,
        ((n_tiles, 128, 128), jnp.bfloat16),
        ((n_tiles,), jnp.int32),
        ((n_tiles,), jnp.int32),
        ((d_in, 8), jnp.bfloat16),
    )


# dropless-MoE shapes: (8, f) capacity blocks against d = 4096, f = 1536
D, F, E, NNZ = 4096, 1536, 8, 256


def test_sdd_compiles_at_moe_widths(one_chip):
    from repro.kernels.bsr_ops import _sdd_pallas

    fn = functools.partial(_sdd_pallas, bm=8, bn=F, interpret=False)
    _compile(
        fn, one_chip,
        ((1024, D), jnp.bfloat16),
        ((D, E * F), jnp.bfloat16),
        ((NNZ,), jnp.int32),
        ((NNZ,), jnp.int32),
    )


def test_dsd_pallas_compiles_at_moe_widths(one_chip):
    from repro.kernels.bsr_ops import dsd
    from repro.sparse.block_csr import BlockMatrix

    M = 1024

    def fn(data, rows, cols, x):
        sp = BlockMatrix.from_coo((M, E * F), (8, F), data, rows, cols)
        return dsd(sp, x, backend="pallas", interpret=False)

    _compile(
        fn, one_chip,
        ((NNZ, 8, F), jnp.bfloat16),
        ((NNZ,), jnp.int32),
        ((NNZ,), jnp.int32),
        ((E * F, D), jnp.bfloat16),
    )


def test_staged_spmv_pallas_program_compiles(one_chip):
    """The whole staged SpMV program (tile gather + kernel + output gather)
    that ``stage_spmv(backend="pallas")`` jits, on a paper-style matrix."""
    from repro.core import vbr as vbrlib
    from repro.core.staging import StagedKernel, StagingOptions

    v = vbrlib.synthesize_paper(20, 20, 40, rows=2000, cols=2000, seed=0)
    kern = StagedKernel("spmv", v, StagingOptions(backend="pallas", interpret=False))
    val = jax.ShapeDtypeStruct(v.val.shape, jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((v.shape[1],), jnp.float32, sharding=one_chip)
    text = kern._fn.lower(val, x).compile().as_text()
    assert "tpu_custom_call" in text
