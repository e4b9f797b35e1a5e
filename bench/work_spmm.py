"""Compulsory device traffic of one sparse x dense product, from the
structure and the width alone: the numerator of the SpMM HBM roofline.
Like ``work.spmv_bytes``, it counts what any implementation moves at
least once, with 4-byte indices and values (int32 / float32)."""
from __future__ import annotations

from bench.work import WORD


def spmm_bytes(nnz: int, num_rows: int, num_cols: int, width: int) -> int:
    """One ``Y = A H`` with ``H`` of ``width`` columns: each nonzero's
    value and column once, one row extent per row, ``H`` read once and
    ``Y`` written once."""
    return (nnz * (WORD + WORD) + num_rows * WORD
            + num_cols * width * WORD + num_rows * width * WORD)
