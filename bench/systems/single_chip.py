"""System ``single_chip``: the served, exact range-filtered search path on
one chip.

``MSTGIndex.build`` with the configured builder -> ``QueryEngine`` with the
configured route -> ``AsyncRetrievalServer`` with its default
``SLOPolicy``. A configuration names its system by this file's name;
modules under ``bench/systems`` are the only ones of the benchmark that
import the program.
"""
from __future__ import annotations

import numpy as np

from repro.core import (EngineConfig, IndexSpec, MSTGIndex, QueryEngine,
                        SearchRequest)
from repro.serving.async_engine import AsyncRetrievalServer


def _embed(items):
    return np.stack(items)


class System:
    """Index, engine and server for one run."""

    def __init__(self, cfg: dict, corpus, k: int, devices):
        spec = IndexSpec(builder=cfg["builder"],
                         variants=tuple(cfg["variants"]))
        self.index = MSTGIndex.build(spec, corpus.vectors, corpus.lo,
                                     corpus.hi)
        self.engine = QueryEngine(self.index,
                                  EngineConfig(route=cfg["route"]))
        self.server = AsyncRetrievalServer(self.engine, _embed, k=k,
                                           route=cfg["route"])
        self.route = cfg["route"]
        self.k = k
        self.max_batch = self.server.scheduler.policy.max_batch

    def submit(self, vector, qlo: float, qhi: float, mask: int):
        return self.server.submit(vector, qlo, qhi, int(mask))

    def step(self):
        return self.server.step()

    @property
    def queued(self) -> int:
        return self.server.scheduler.depth

    def search(self, vectors, qlo, qhi, mask: int):
        """One batch straight through the engine, as a round of the server
        sends it (used to warm the programs up)."""
        req = SearchRequest(np.ascontiguousarray(vectors, np.float32),
                            (np.asarray(qlo, np.float64),
                             np.asarray(qhi, np.float64)),
                            int(mask), k=self.k, route=self.route)
        return self.engine.execute(req)

    def scan_shapes(self, mask: int, qlo, qhi) -> np.ndarray:
        """(Q, slots) scan length class of each request in each plan slot,
        as the engine sizes it alone: the number of candidate rows up to the
        slot's version, rounded up to a power of two and capped at n."""
        eng = self.engine
        n = self.index.vectors.shape[0]
        cols = []
        for s in eng.plan(int(mask), np.asarray(qlo), np.asarray(qhi)):
            ranks = np.sort(self.index.variants[s.variant].sort_rank)
            cap = np.searchsorted(ranks, s.version, side="right")
            pow2 = 1 << np.ceil(np.log2(np.maximum(cap, 1))).astype(np.int64)
            cols.append(np.where(cap > 0, np.minimum(pow2, n), 0))
        return np.stack(cols, axis=1)

    def close(self):
        """Drop every reference to the program's state so that its device
        buffers are freed."""
        self.server = self.engine = self.index = None
