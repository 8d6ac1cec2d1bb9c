"""Warm-up: run every program shape the window can meet, before it opens.

A round of the server sends each predicate's group of queries to the
engine as one batch. The engine pads a batch to a power-of-two bucket (at
least 8) and sizes each plan slot's scan by the longest candidate prefix in
the batch, so a batch meets the program of (mask, bucket, longest scan of
each slot). For every mask of the mix this runs, at every bucket the mix
can produce, batches of copies of requests that together give each slot
every scan length its requests have alone: the longest scan of a mixed
batch is one of those. Then one batch of every group size the mix can
produce covers what depends on the exact size.
"""
from __future__ import annotations

import numpy as np


def group_sizes(mix: dict, max_batch: int) -> list:
    """Sizes of one predicate's group in a round.

    Masks are sent round-robin and the queue is first-in first-out, so a
    round of r requests takes r consecutive ones and gives each mask the
    floor or the ceiling of r / M. A closed loop keeps at least
    ``max_batch`` requests queued, so its rounds are all ``max_batch``
    long; an open loop's rounds can be anything up to that."""
    M = len(mix["masks"])
    if mix["loop"] == "closed" and mix["outstanding"] >= max_batch:
        rounds = [max_batch]
    else:
        rounds = range(1, max_batch + 1)
    return sorted({g for r in rounds for g in (r // M, -(-r // M)) if g})


def _bucket(q: int) -> int:
    return max(8, 1 << max(q - 1, 0).bit_length())


def _cover(shapes: np.ndarray) -> list:
    """Rows of ``shapes`` (requests x slots) that together show every
    (slot, length) pair it holds, picked greedily."""
    need = {(s, int(c)) for s in range(shapes.shape[1])
            for c in np.unique(shapes[:, s]) if c > 0}
    picks = []
    while need:
        best = max(range(shapes.shape[0]),
                   key=lambda r: sum((s, int(shapes[r, s])) in need
                                     for s in range(shapes.shape[1])))
        picks.append(best)
        need -= {(s, int(shapes[best, s])) for s in range(shapes.shape[1])}
    return picks


def warm(system, requests, vectors, mix: dict) -> int:
    """Run the warm-up batches; returns how many were run."""
    sizes = group_sizes(mix, system.max_batch)
    buckets = sorted({_bucket(g) for g in sizes})
    runs = 0
    cheapest = None
    for m in np.unique(requests.mask):
        rows = np.flatnonzero(requests.mask == m)
        shapes = system.scan_shapes(m, requests.qlo[rows], requests.qhi[rows])
        for p in _cover(shapes):
            r = rows[p]
            for b in buckets:
                idx = np.full(b, r)
                system.search(vectors[requests.vector[idx]],
                              requests.qlo[idx], requests.qhi[idx], m)
                runs += 1
        light = rows[np.argmin(shapes.sum(axis=1))]
        if cheapest is None or shapes.sum(axis=1).min() < cheapest[0]:
            cheapest = (shapes.sum(axis=1).min(), light)
    r = cheapest[1]
    for g in sizes:
        idx = np.full(g, r)
        system.search(vectors[requests.vector[idx]], requests.qlo[idx],
                      requests.qhi[idx], requests.mask[r])
        runs += 1
    return runs
