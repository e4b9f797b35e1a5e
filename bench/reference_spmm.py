"""Plain reference of the SpMM cells and their control.  Nothing here
imports the program under test.

* ``spmm_reference``: ``Y = A H`` in float64 over the CSR, for a sample
  of rows, in blocks of nonzeros so that the terms stay small, with each
  entry's summed term magnitudes ``sum_j |a_ij h_jd|``; the error is
  reckoned by ``reference.spmv_error``, entry by entry.
* ``spmm_bf16``, the control: the same product in bfloat16 (the
  precision below the configuration's float32), on the device.
"""
from __future__ import annotations

import numpy as np

# nonzeros a reference block multiplies at once: 2^16 x 256 float64
# terms are 128 MiB
BLOCK_NNZ = 1 << 16
# nonzeros the control gathers at once
CONTROL_CHUNK = 1 << 20


def sample_rows(struct, rng: np.random.Generator, count: int,
                top: int) -> np.ndarray:
    """Sorted distinct rows to compare: ``count`` drawn by ``rng`` and the
    ``top`` of highest degree, whose long sums round the most."""
    m = struct.shape[0]
    drawn = rng.choice(m, size=min(count, m), replace=False)
    heavy = np.argsort(struct.degree, kind="stable")[-top:] if top else []
    return np.unique(np.concatenate([drawn, heavy])).astype(np.int64)


def _positions(struct, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Flat nonzero positions of ``rows`` (CSR order) and, for each, the
    index of its row in ``rows``."""
    lo = struct.indptr[rows]
    counts = struct.indptr[rows + 1] - lo
    first = np.cumsum(counts) - counts
    pos = np.repeat(lo - first, counts) + np.arange(int(counts.sum()))
    return pos, np.repeat(np.arange(rows.shape[0]), counts)


def needed_cols(struct, rows: np.ndarray) -> np.ndarray:
    """Sorted distinct columns the rows of ``rows`` read."""
    return np.unique(struct.cols[_positions(struct, rows)[0]])


def spmm_reference(struct, rows: np.ndarray, cols: np.ndarray,
                   h_cols: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``((A H)[rows], sum_j |a_ij h_jd|)`` in float64, where ``h_cols``
    holds the rows ``cols`` (sorted, :func:`needed_cols`) of ``H``."""
    pos, which = _positions(struct, rows)
    d = h_cols.shape[1]
    ref = np.zeros((rows.shape[0], d))
    absum = np.zeros((rows.shape[0], d))
    for s in range(0, pos.shape[0], BLOCK_NNZ):
        p, w = pos[s:s + BLOCK_NNZ], which[s:s + BLOCK_NNZ]
        h = h_cols[np.searchsorted(cols, struct.cols[p])]
        prod = struct.vals[p].astype(np.float64)[:, None] * h
        starts = np.flatnonzero(np.concatenate([[True], w[1:] != w[:-1]]))
        ref[w[starts]] += np.add.reduceat(prod, starts, axis=0)
        absum[w[starts]] += np.add.reduceat(np.abs(prod), starts, axis=0)
    return ref, absum


def spmm_bf16(struct):
    """The product in bfloat16, on the device: ``H -> Y`` (float32),
    accumulated in bfloat16 over chunks of :data:`CONTROL_CHUNK`
    nonzeros."""
    import jax
    import jax.numpy as jnp
    m = struct.shape[0]
    pad = (-struct.nnz) % CONTROL_CHUNK

    def chunks(a, dtype):
        return jnp.asarray(np.pad(a, (0, pad)).reshape(-1, CONTROL_CHUNK),
                           dtype)
    rows = chunks(struct.rows, jnp.int32)
    cols = chunks(struct.cols, jnp.int32)
    vals = chunks(struct.vals, jnp.bfloat16)       # pads add 0 to row 0

    @jax.jit
    def run(r, c, v, h):
        hb = h.astype(jnp.bfloat16)

        def body(y, rcv):
            r, c, v = rcv
            return y.at[r].add(v[:, None] * hb[c]), None
        y, _ = jax.lax.scan(body, jnp.zeros((m, h.shape[1]), jnp.bfloat16),
                            (r, c, v))
        return y.astype(jnp.float32)
    return lambda h: run(rows, cols, vals, h)
