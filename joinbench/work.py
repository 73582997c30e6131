"""The bytes that a join must move, whatever implements it.

Every input column and validity mask is read once, and every output
row's columns are written once. Nothing of the program's algorithm (its
sorts, scans, buffers or passes) enters, so the share of the HBM peak
that this gives still bounds a claim after a kernel is replaced.
"""

from __future__ import annotations


def input_bytes(sides) -> int:
    """Bytes of every column and validity mask of the input tables;
    ``sides`` holds ``(columns, valid)`` pairs of arrays or shapes."""
    return sum(a.size * a.dtype.itemsize
               for cols, valid in sides
               for a in (*cols.values(), valid))


def output_row_bytes(dtypes) -> int:
    """Bytes of one output row with columns of the given dtypes."""
    return sum(d.itemsize for d in dtypes)


def hbm_bytes_per_call(in_bytes: int, row_bytes: int,
                       matches: float) -> float:
    return in_bytes + row_bytes * matches
