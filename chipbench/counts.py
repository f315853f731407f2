"""Operations and bytes of the sDTW recurrence, from shapes alone.

A DP cell is one (query sample, reference sample) pair.  Its own work is
five VPU operations: the subtraction and the square of the squared
Euclidean cost, two minimums over the three predecessors, and the add.
Padding rows, skipped blocks and a plan's bookkeeping are not counted,
so the count is the same whatever implements the recurrence.

The bytes are the least traffic a call needs: every query and every
reference sample read once, every output written once, all float32 or
int32.
"""

from __future__ import annotations

OPS_PER_CELL = 5
WORD = 4                                  # float32 / int32 bytes


def cells(pairs: int, m: int, n: int) -> int:
    """DP cells of ``pairs`` (query, reference) alignments."""
    return pairs * m * n


def sdtw_ops(n_cells: int) -> int:
    return OPS_PER_CELL * n_cells


def sdtw_bytes(*, queries: int, m: int, references: int, n: int,
               outputs: int) -> int:
    """Bytes in and out of ``queries`` alignments of length-``m``
    queries against ``references`` length-``n`` references, with
    ``outputs`` values (cost, end, start) returned per alignment."""
    return WORD * (queries * m + references * n + queries * outputs)


def roofline_s(*, ops: float, bytes_: float, peaks: dict) -> tuple:
    """(least seconds, the term that bounds it: "ops" or "bytes")."""
    t_ops = ops / peaks["vpu_ops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
