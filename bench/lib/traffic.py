"""One generator for every traffic mix: a mix is data, read from
``bench/traffic/<name>.json``.

Keys of a mix:

- ``loop``: ``"closed"`` (``outstanding`` requests always in the server;
  each answer sends the next request) or ``"open"`` (arrivals at
  ``rate_qps``, sent on their schedule whatever the server does);
- ``masks``: predicate names, assigned to requests round-robin;
- ``selectivity``: the share of the corpus each request's predicate keeps,
  held within ``selectivity_tol`` of it (relative) by an exact count;
- ``k``: neighbours per request;
- ``pool``: distinct requests a closed loop cycles through, a multiple of
  the number of masks so that the round-robin holds across the wrap.

Each request's query interval is a pair of grid values [grid[i], grid[j]]
drawn uniformly from all pairs whose exact count for its mask is within
the tolerance. Open-loop arrivals are a Poisson process conditioned on its
count: ``round(rate_qps * seconds)`` due times drawn uniformly over the
window and sorted, so every seed offers the same number of requests.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import data, predicate


@dataclasses.dataclass
class Requests:
    mask: np.ndarray       # (R,) int predicate bits
    qlo: np.ndarray        # (R,) float64 grid values
    qhi: np.ndarray
    qi: np.ndarray         # (R,) grid positions of qlo / qhi
    qj: np.ndarray
    count: np.ndarray      # (R,) exact number of objects the predicate keeps
    vector: np.ndarray     # (R,) row of Corpus.queries
    due: np.ndarray        # (R,) seconds after the window opens (open loop)


def request_count(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return int(round(mix["rate_qps"] * seconds))
    if mix["pool"] % len(mix["masks"]):
        raise ValueError("a closed loop's pool must be a multiple of its "
                         "number of masks")
    return int(mix["pool"])


def make_requests(mix: dict, corpus: data.Corpus, seed: int,
                  seconds: float) -> Requests:
    n = corpus.lo.shape[0]
    K = corpus.grid.shape[0]
    R = request_count(mix, seconds)
    rng = data.host_rng(seed, 2)
    target = mix["selectivity"] * n
    tol = mix["selectivity_tol"] * target
    masks = [predicate.MASKS[m] for m in mix["masks"]]
    mask = np.array([masks[r % len(masks)] for r in range(R)], np.int64)
    qi = np.empty(R, np.int64)
    qj = np.empty(R, np.int64)
    count = np.empty(R, np.int64)
    for m in masks:
        c = predicate.grid_counts(corpus.lo_pos, corpus.hi_pos, K, m)
        ii, jj = np.nonzero((np.abs(c - target) <= tol)
                            & (np.arange(K)[:, None] <= np.arange(K)[None]))
        if ii.size == 0:
            raise ValueError(f"mask {m}: no query interval keeps "
                             f"{mix['selectivity']:.4f} of the corpus "
                             f"within {mix['selectivity_tol']:.2f}")
        rows = np.flatnonzero(mask == m)
        pick = rng.integers(0, ii.size, rows.size)
        qi[rows], qj[rows] = ii[pick], jj[pick]
        count[rows] = c[ii[pick], jj[pick]]
    if mix["loop"] == "open":
        due = np.sort(rng.uniform(0.0, seconds, R))
    else:
        due = np.zeros(R)
    vector = np.arange(R) % corpus.queries.shape[0]
    return Requests(mask, corpus.grid[qi], corpus.grid[qj], qi, qj, count,
                    vector, due)
