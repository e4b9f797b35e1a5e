"""A GCN layer's normalised adjacency over the Graph500 Kronecker graph.

The graph is ``kronecker.make`` of the configuration's own Graph500
parameters: both directions of every edge tuple, row-major sorted,
duplicates kept.  As PyG's ``add_remaining_self_loops`` does, every
vertex that has no self-loop gets one ``(i, i)``; then the entries are
sorted again.  The values are Kipf & Welling's renormalised adjacency
``D^-1/2 (A + I) D^-1/2`` as PyG's ``gcn_norm`` builds it: entry
``(i, j)`` holds ``1 / sqrt(deg_i * deg_j)``, with the degrees counted
over the result, duplicates and self-loops included.  The graph is
symmetric, so row and column degrees are one and the same.
"""
from __future__ import annotations

import numpy as np

from bench.generators import kronecker
from bench.structure import Structure


def make(cfg: dict) -> Structure:
    g = kronecker.make(cfg)
    n = g.shape[0]
    looped = np.zeros(n, bool)
    looped[g.rows[g.rows == g.cols]] = True
    lonely = np.flatnonzero(~looped).astype(np.int64)
    keys = np.concatenate([(g.rows.astype(np.int64) << 32) | g.cols,
                           (lonely << 32) | lonely])
    del g
    keys.sort()
    rows, cols = (keys >> 32).astype(np.int32), keys.astype(np.int32)
    del keys
    inv_sqrt = 1.0 / np.sqrt(np.bincount(rows, minlength=n))
    vals = (inv_sqrt[rows] * inv_sqrt[cols]).astype(np.float32)
    return Structure(rows=rows, cols=cols, vals=vals, shape=(n, n))
