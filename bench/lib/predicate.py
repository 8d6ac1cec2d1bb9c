"""The range-range predicates, written plainly, and exact counts over a grid.

The four atomic relations between an object interval [lo, hi] and a query
interval [ql, qh] (paper Fig. 1) and their union:

    left_overlap      lo <= ql <= hi <= qh
    query_contained   lo <= ql,  qh <= hi
    right_overlap     ql <= lo <= qh <= hi
    query_containing  ql <= lo,  hi <= qh
    any_overlap       lo <= qh and ql <= hi      (the union of the four)

The bit values match the program's encoding, so a mask travels to the
server as an int. Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

LEFT_OVERLAP = 1
QUERY_CONTAINED = 2
RIGHT_OVERLAP = 4
QUERY_CONTAINING = 8
ANY_OVERLAP = 15

MASKS = {
    "left_overlap": LEFT_OVERLAP,
    "query_contained": QUERY_CONTAINED,
    "right_overlap": RIGHT_OVERLAP,
    "query_containing": QUERY_CONTAINING,
    "any_overlap": ANY_OVERLAP,
}


def holds(mask, lo, hi, ql, qh, xp=np):
    """Truth of ``mask`` for broadcastable object and query endpoints.

    ``mask`` may be an int or an integer array broadcastable against the
    endpoints (one mask per query)."""
    m = xp.asarray(mask)

    def bit(b):
        return (m & b) != 0

    return ((bit(LEFT_OVERLAP) & (lo <= ql) & (ql <= hi) & (hi <= qh))
            | (bit(QUERY_CONTAINED) & (lo <= ql) & (qh <= hi))
            | (bit(RIGHT_OVERLAP) & (ql <= lo) & (lo <= qh) & (qh <= hi))
            | (bit(QUERY_CONTAINING) & (ql <= lo) & (hi <= qh)))


def grid_counts(lo_pos: np.ndarray, hi_pos: np.ndarray, K: int, mask: int
                ) -> np.ndarray:
    """(K, K) exact count of objects satisfying ``mask`` for every query
    [grid[i], grid[j]], from the K x K histogram of endpoint grid positions.

    Every mask here is one rectangle in (lo position, hi position) space, so
    a 2-D prefix sum answers all K*K queries at once. Entries with i > j are
    not queries and hold 0."""
    H = np.zeros((K, K), np.int64)
    np.add.at(H, (lo_pos, hi_pos), 1)
    P = np.zeros((K + 1, K + 1), np.int64)
    P[1:, 1:] = H.cumsum(0).cumsum(1)
    i = np.arange(K)[:, None]
    j = np.arange(K)[None, :]

    def rect(a0, a1, b0, b1):
        a0, a1, b0, b1 = np.broadcast_arrays(a0, a1, b0, b1)
        a1c, b1c = a1 + 1, b1 + 1
        c = P[a1c, b1c] - P[a0, b1c] - P[a1c, b0] + P[a0, b0]
        return np.where((a1 >= a0) & (b1 >= b0), c, 0)

    zero, top = np.zeros_like(i), np.full_like(i, K - 1)
    if mask == LEFT_OVERLAP:
        out = rect(zero, i, i, j)
    elif mask == QUERY_CONTAINED:
        out = rect(zero, i, j, top)
    elif mask == RIGHT_OVERLAP:
        out = rect(i, j, j, top)
    elif mask == QUERY_CONTAINING:
        out = rect(i, j, zero, j)
    elif mask == ANY_OVERLAP:
        out = rect(zero, j, i, top)
    else:
        raise ValueError(f"no grid count for mask {mask}")
    return np.where(i <= j, out, 0)
