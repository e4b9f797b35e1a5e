"""The generated matrix or graph a cell runs on."""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


@dataclasses.dataclass
class Structure:
    """Row-major sorted COO (int32 indices, float32 values).

    For a graph, ``rows``/``cols`` hold both directions of every input
    edge tuple, so row ``r``'s columns are ``r``'s neighbours, and
    ``tuples`` keeps the generator's ``(M, 2)`` edge list, from which
    Graph500 counts the edges a search traversed."""
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    shape: tuple[int, int]
    tuples: np.ndarray | None = None

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @functools.cached_property
    def indptr(self) -> np.ndarray:
        """CSR row offsets (int64) of the sorted COO."""
        counts = np.bincount(self.rows, minlength=self.shape[0])
        out = np.zeros(self.shape[0] + 1, np.int64)
        np.cumsum(counts, out=out[1:])
        return out

    @functools.cached_property
    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)
