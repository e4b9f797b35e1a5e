"""Plain references the benchmark compares the timed path with, and the
controls that must fail that comparison.  Nothing here imports the
program under test.

* SpMV: float64 ``bincount`` over the COO, with each row's summed term
  magnitudes, the scale a float32 sum's rounding error grows with.
* BFS: top-down frontier search over the CSR (each edge looked at once).
* Controls: the SpMV reference computed in bfloat16 (the precision below
  the configuration's float32), and the BFS reference stopped one level
  short (a fixpoint loop that ends a sweep too early).
"""
from __future__ import annotations

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
TINY32 = float(np.finfo(np.float32).tiny)


def spmv_reference(struct, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(A x, sum_j |a_ij x_j|)`` per row, in float64."""
    prod = struct.vals.astype(np.float64) * x.astype(np.float64)[struct.cols]
    m = struct.shape[0]
    return (np.bincount(struct.rows, weights=prod, minlength=m),
            np.bincount(struct.rows, weights=np.abs(prod), minlength=m))


def spmv_error(y: np.ndarray, ref: np.ndarray, absum: np.ndarray) -> float:
    """Largest row error in float32 units of that row's summed term
    magnitudes: ``max |y - ref| / (eps32 * sum|a x|)``; inf where ``y``
    is not finite."""
    y = np.asarray(y, np.float64)
    if not np.all(np.isfinite(y)):
        return float("inf")
    return float(np.max(np.abs(y - ref) / (EPS32 * absum + TINY32),
                        initial=0.0))


def spmv_bf16(struct):
    """The reference in bfloat16, on the device: ``x -> y`` (float32)."""
    import jax
    import jax.numpy as jnp
    m = struct.shape[0]
    rows, cols = jnp.asarray(struct.rows), jnp.asarray(struct.cols)
    vals = jnp.asarray(struct.vals, jnp.bfloat16)

    @jax.jit
    def run(r, c, v, x):
        y = jax.ops.segment_sum(v * x.astype(jnp.bfloat16)[c], r,
                                num_segments=m, indices_are_sorted=True)
        return y.astype(jnp.float32)
    return lambda x: run(rows, cols, vals, x)


def bfs_reference(indptr: np.ndarray, cols: np.ndarray, root: int
                  ) -> np.ndarray:
    """int32 levels from ``root``; -1 where unreached."""
    level = np.full(indptr.shape[0] - 1, -1, np.int32)
    level[root] = 0
    frontier = np.asarray([root], np.int64)
    depth = 0
    while frontier.size:
        starts = indptr[frontier]
        counts = indptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        # flat positions of every frontier vertex's neighbour list
        first = np.cumsum(counts) - counts
        pos = np.repeat(starts - first, counts) + np.arange(total)
        nbrs = cols[pos]
        nbrs = nbrs[level[nbrs] < 0]
        depth += 1
        level[nbrs] = depth
        frontier = np.flatnonzero(level == depth)
    return level


def bfs_one_level_short(indptr: np.ndarray, cols: np.ndarray, root: int
                        ) -> np.ndarray:
    """The control: the reference with its deepest level left unreached."""
    level = bfs_reference(indptr, cols, root)
    deepest = level.max()
    if deepest > 0:
        level[level == deepest] = -1
    return level
