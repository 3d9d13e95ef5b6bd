"""Plain reference for a VBR (variable block row) matrix configuration.

It imports nothing of the program.  It builds the matrix's structure from
the configuration (a copy of the structure half of the paper's generator,
``repro.core.vbr.synthesize``: the same draws from the same seed), makes the
values and the dense operand on the device from ``--seed``, and computes
products block by block in float64 on the host.

The storage convention is the VBR format's (SABLE, arXiv:2407.00829, Fig. 3):
blocks are stored densely, column-major inside a block, in block-row order;
``indx[b]`` is the offset of block ``b`` in ``val``.

``rel_err`` is the comparison that decides ``correct``: the largest
``|y - A x| / (|A| |x|)`` over the entries of one product, where ``A x``
and ``|A| |x|`` are float64.  ``control_kernel`` is the reference put in
the program's place and computed in a lower precision than the
configuration states; it exists only for the control runs.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Structure:
    shape: tuple
    rpntr: np.ndarray
    cpntr: np.ndarray
    bindx: np.ndarray
    bpntrb: np.ndarray
    bpntre: np.ndarray
    indx: np.ndarray

    @property
    def stored_nnz(self) -> int:
        return int(self.indx[-1])

    @property
    def num_blocks(self) -> int:
        return len(self.bindx)

    def blocks(self):
        """(row0, row1, col0, col1, val_offset) of every stored block."""
        b = 0
        for a in range(len(self.rpntr) - 1):
            if self.bpntrb[a] == -1:
                continue
            r0, r1 = int(self.rpntr[a]), int(self.rpntr[a + 1])
            for bi in range(int(self.bpntrb[a]), int(self.bpntre[a])):
                c = int(self.bindx[bi])
                yield r0, r1, int(self.cpntr[c]), int(self.cpntr[c + 1]), int(self.indx[b])
                b += 1


def _split_points(n: int, parts: int, uniform: bool, rng) -> np.ndarray:
    if parts >= n:
        return np.arange(n + 1, dtype=np.int32)
    if uniform:
        return np.linspace(0, n, parts + 1).round().astype(np.int32)
    cuts = np.sort(rng.choice(np.arange(1, n), size=parts - 1, replace=False))
    return np.concatenate([[0], cuts, [n]]).astype(np.int32)


def make_structure(cfg: dict) -> Structure:
    """The matrix's structure from its configuration (never from --seed)."""
    rows, cols = int(cfg["rows"]), int(cfg["cols"])
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    rpntr = _split_points(rows, int(cfg["row_splits"]), bool(cfg["uniform"]), rng)
    cpntr = _split_points(cols, int(cfg["col_splits"]), bool(cfg["uniform"]), rng)
    R, C = len(rpntr) - 1, len(cpntr) - 1
    n = min(int(cfg["num_blocks"]), R * C)
    chosen = np.sort(rng.choice(R * C, size=n, replace=False))
    by_row: dict[int, list[int]] = {}
    for cell in chosen:
        by_row.setdefault(int(cell) // C, []).append(int(cell) % C)
    bindx, bpntrb, bpntre, indx = [], [], [], [0]
    for a in range(R):
        h = int(rpntr[a + 1] - rpntr[a])
        blocks = by_row.get(a)
        if not blocks:
            bpntrb.append(-1)
            bpntre.append(-1)
            continue
        bpntrb.append(len(bindx))
        for b in blocks:
            bindx.append(b)
            indx.append(indx[-1] + h * int(cpntr[b + 1] - cpntr[b]))
        bpntre.append(len(bindx))
    return Structure(
        shape=(rows, cols), rpntr=rpntr, cpntr=cpntr,
        bindx=np.asarray(bindx, np.int32), bpntrb=np.asarray(bpntrb, np.int32),
        bpntre=np.asarray(bpntre, np.int32), indx=np.asarray(indx, np.int64),
    )


def make_values(st: Structure, key):
    """Standard normal float32 values of every stored block, on the device,
    in one jitted call."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: jax.random.normal(k, (st.stored_nnz,), jnp.float32))(key)


def make_operand(st: Structure, n_cols, key):
    """The dense operand: (k,) for SpMV, (k, n_cols) for SpMM."""
    import jax
    import jax.numpy as jnp

    shape = (st.shape[1],) if n_cols is None else (st.shape[1], int(n_cols))
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)


class Reference:
    """float64 products ``A x`` and error scales ``|A| |x|``, block by block."""

    def __init__(self, st: Structure, val: np.ndarray):
        val = np.asarray(val, np.float64)
        self.shape = st.shape
        self.blocks = []
        for r0, r1, c0, c1, off in st.blocks():
            h, w = r1 - r0, c1 - c0
            blk = val[off:off + h * w].reshape(w, h).T  # column-major block
            self.blocks.append((r0, r1, c0, c1, blk, np.abs(blk)))

    def product(self, x: np.ndarray):
        x = np.asarray(x, np.float64)
        ax = np.abs(x)
        out_shape = (self.shape[0],) + x.shape[1:]
        y = np.zeros(out_shape)
        s = np.zeros(out_shape)
        for r0, r1, c0, c1, blk, ablk in self.blocks:
            y[r0:r1] += blk @ x[c0:c1]
            s[r0:r1] += ablk @ ax[c0:c1]
        return y, s

    def rel_err(self, x, y) -> float:
        """Largest |y - A x| / (|A| |x|) over the entries; inf if ``y`` has
        the wrong shape or a non-finite entry."""
        y = np.asarray(y, np.float64)
        ref, scale = self.product(x)
        if y.shape != ref.shape or not np.all(np.isfinite(y)):
            return float("inf")
        err = np.abs(y - ref) / np.maximum(scale, 1e-300)
        return float(err.max()) if err.size else 0.0


def _round(a, dtype):
    """Round ``a`` to ``dtype`` and back to float32; float8 takes one scale
    per tensor so that its range covers the tensor.  bfloat16 goes through
    ``reduce_precision``: XLA may drop a float32 -> bfloat16 -> float32
    round trip of converts (excess precision), and did on the TPU."""
    import jax
    import jax.numpy as jnp

    if jnp.dtype(dtype) == jnp.dtype(jnp.float8_e4m3fn):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / scale).astype(dtype).astype(jnp.float32) * scale
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"no control rounding for {dtype}")


def control_kernel(st: Structure, dtype: str):
    """``fn(val, x) -> y``: the dense product with ``val`` and ``x`` rounded
    to ``dtype`` and float32 accumulation, on the device."""
    import jax
    import jax.numpy as jnp

    rows = np.empty(st.stored_nnz, np.int32)
    cols = np.empty(st.stored_nnz, np.int32)
    for r0, r1, c0, c1, off in st.blocks():
        h, w = r1 - r0, c1 - c0
        j = np.arange(h * w)
        rows[off:off + h * w] = r0 + j % h
        cols[off:off + h * w] = c0 + j // h
    rows_d, cols_d = jnp.asarray(rows), jnp.asarray(cols)
    m, k = st.shape

    @jax.jit
    def dense_product(val, x, r, c):
        a = jnp.zeros((m, k), jnp.float32).at[r, c].set(_round(val, dtype))
        return jnp.dot(a, _round(x, dtype), precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    return lambda val, x: dense_product(val, x, rows_d, cols_d)
