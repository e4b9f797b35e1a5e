"""Compulsory device traffic of one operation, from the structure alone.

These are the numerators of the HBM rooflines.  They count what any
implementation has to move at least once, whatever its lowering, so a
change of plan, kernel or traversal order leaves them valid.  Every index
and value is 4 bytes (int32 / float32).
"""
from __future__ import annotations

WORD = 4


def spmv_bytes(nnz: int, num_rows: int, num_cols: int) -> int:
    """One ``y = A x``: each nonzero's value and column once, one row
    extent per row, x read once, y written once."""
    return (nnz * (WORD + WORD) + num_rows * WORD + num_cols * WORD
            + num_rows * WORD)


def bfs_bytes(component_nnz: int, num_nodes: int) -> int:
    """One BFS: each directed edge of the reached component once, each
    vertex's level read and written once, and the CSR offsets once."""
    return (component_nnz * WORD + num_nodes * (WORD + WORD)
            + (num_nodes + 1) * WORD)
