"""HPCG's 27-point stencil on an ``nx x ny x nz`` grid (one process's
subgrid, no halo).

Follows HPCG's ``GenerateProblem``: row ``ix + nx*(iy + ny*iz)``; the
neighbours in the order ``sz, sy, sx`` from -1 to 1, each kept when it
lies in the grid; diagonal ``26``, off-diagonal ``-1``.  That order makes
each row's columns ascending, so the COO is row-major sorted as built.
"""
from __future__ import annotations

import numpy as np

from bench.structure import Structure


def make(cfg: dict) -> Structure:
    nx, ny, nz = int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])
    n = nx * ny * nz
    r = np.arange(n, dtype=np.int32)
    ix, iy, iz = r % nx, (r // nx) % ny, r // (nx * ny)
    offsets = [(sz, sy, sx) for sz in (-1, 0, 1) for sy in (-1, 0, 1)
               for sx in (-1, 0, 1)]
    cols = np.empty((n, len(offsets)), np.int32)
    keep = np.empty((n, len(offsets)), bool)
    vals = np.empty(len(offsets), np.float32)
    for k, (sz, sy, sx) in enumerate(offsets):
        keep[:, k] = ((ix + sx >= 0) & (ix + sx < nx) & (iy + sy >= 0)
                      & (iy + sy < ny) & (iz + sz >= 0) & (iz + sz < nz))
        cols[:, k] = r + sz * nx * ny + sy * nx + sx
        vals[k] = (cfg["diagonal"] if (sz, sy, sx) == (0, 0, 0)
                   else cfg["off_diagonal"])
    rows = np.broadcast_to(r[:, None], keep.shape)[keep]
    v = np.broadcast_to(vals[None, :], keep.shape)[keep]
    return Structure(rows=rows, cols=cols[keep], vals=v, shape=(n, n))
