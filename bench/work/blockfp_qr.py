"""Lower-bound work of one block-FP CORDIC QR decomposition.

Counted from shapes alone, for the rotations any Givens implementation
must perform on a dense m x n matrix, so that no kernel can do the
decomposition with less:

* Every subdiagonal entry (r, c) is annihilated by a rotation whose
  leading column is c.  In a dense matrix both rows it combines are
  nonzero in every column c..n-1, so it rotates n - c element pairs of
  the R part.  Column c has m - 1 - c such entries (c < min(m - 1, n)).
* With Q, each rotation also combines two rows of the accumulating
  orthogonal factor.  Two rows of an orthogonal matrix are linearly
  independent, so together they are nonzero in at least two columns:
  at least two more element pairs per rotation.
* Per element pair and CORDIC micro-rotation: two shifts and two adds
  (x -/+ y >> i, y +/- x >> i).  Per element, one gain-compensation
  multiply.  Direction decisions, sign handling, rounding and the
  encode/decode around the kernel are not counted.

Bytes: the kernel's operand and result, one int32 word per element of
the (m, n [+ m]) working matrix, each moved once.  Stage tables are not
counted.
"""
from __future__ import annotations

WORD_BYTES = 4


def rotations(m: int, n: int) -> int:
    """Givens rotations that annihilate the subdiagonal of an m x n matrix."""
    return sum(m - 1 - c for c in range(min(m - 1, n)))


def pairs(m: int, n: int, compute_q: bool) -> int:
    """Element pairs every implementation must rotate (see module doc)."""
    r_part = sum((m - 1 - c) * (n - c) for c in range(min(m - 1, n)))
    return r_part + (2 * rotations(m, n) if compute_q else 0)


def ops(m: int, n: int, compute_q: bool, iters: int) -> int:
    """int32 vector operations of one decomposition, at least."""
    return pairs(m, n, compute_q) * (4 * iters + 2)


def bytes_moved(m: int, n: int, compute_q: bool) -> int:
    """HBM bytes of one decomposition's kernel operand and result."""
    e = n + m if compute_q else n
    return 2 * m * e * WORD_BYTES
