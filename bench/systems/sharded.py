"""System ``sharded``: the served, exact range-filtered search path over a
corpus range-sharded across the cell's chips.

``ShardedDeployment.build`` (``shards`` contiguous row ranges, shard ``i``
on device ``i`` of ``jax.devices()`` round-robin, one attribute domain)
with the configured builder and route -> ``AsyncRetrievalServer`` with its
default ``SLOPolicy``. Every engine call plans once and fans out over all
the shards. On a CPU rehearsal with one device every shard shares it.
"""
from __future__ import annotations

import numpy as np

from repro.core import EngineConfig, IndexSpec, SearchRequest
from repro.distributed import DeploymentSpec, ShardedDeployment
from repro.serving.async_engine import AsyncRetrievalServer


def _embed(items):
    return np.stack(items)


class System:
    """Deployment and server for one run."""

    def __init__(self, cfg: dict, corpus, k: int, devices):
        spec = DeploymentSpec(
            n_shards=int(cfg["shards"]),
            engine=EngineConfig(route=cfg["route"]),
            index=IndexSpec(builder=cfg["builder"],
                            variants=tuple(cfg["variants"])))
        self.engine = ShardedDeployment.build(corpus.vectors, corpus.lo,
                                              corpus.hi, spec=spec)
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
        """One batch straight through the deployment, as a round of the
        server sends it (used to warm the programs up)."""
        req = SearchRequest(np.ascontiguousarray(vectors, np.float32),
                            (np.asarray(qlo, np.float64),
                             np.asarray(qhi, np.float64)),
                            int(mask), k=self.k, route=self.route)
        return self.engine.execute(req)

    def scan_shapes(self, mask: int, qlo, qhi) -> np.ndarray:
        """(Q, shards x slots) scan length class of each request in each
        shard's plan slot, as that shard's engine sizes it alone: the
        shard's candidate rows up to the slot's version, rounded up to a
        power of two and capped at the shard's rows. Each shard runs its
        own programs on its own chip, so each is a column."""
        slots = self.engine.plan(int(mask), np.asarray(qlo), np.asarray(qhi))
        cols = []
        for shard in self.engine.shards:
            index = shard.engine.index
            n = index.vectors.shape[0]
            for s in slots:
                ranks = np.sort(index.variants[s.variant].sort_rank)
                cap = np.searchsorted(ranks, s.version, side="right")
                pow2 = 1 << np.ceil(np.log2(np.maximum(cap, 1))).astype(
                    np.int64)
                cols.append(np.where(cap > 0, np.minimum(pow2, n), 0))
        return np.stack(cols, axis=1)

    def close(self):
        """Drop every reference to the deployment, and with it every
        shard's device buffers."""
        self.server = self.engine = None
