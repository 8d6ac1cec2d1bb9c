"""Vector compression walkthrough: build a quantized index (int8 codes +
exact float32 re-rank), compare recall and bytes against the float32 tier,
and show the rerank_k knob and quantized save/load.

    PYTHONPATH=src python examples/vector_compression.py
"""
import sys
import tempfile
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import (EngineConfig, IndexSpec, MSTGIndex, Overlaps,
                        QueryEngine, SearchRequest)
from repro.data import brute_force_topk, make_queries, make_range_dataset, \
    recall_at_k


def main():
    ds = make_range_dataset(n=60_000, d=64, n_queries=16, quantize=64, seed=0)
    qlo, qhi = make_queries(ds, Overlaps().mask, 0.5, seed=1)
    tids, _ = brute_force_topk(ds.vectors, ds.lo, ds.hi, ds.queries,
                               qlo, qhi, Overlaps().mask, 10)
    req = SearchRequest(ds.queries, (qlo, qhi), Overlaps().mask, k=10,
                        route="flat")

    # 1. one build per storage tier — the tier lives on the IndexSpec, so it
    # persists and streams with the index
    print(f"{'tier':>8} {'scan MB':>8} {'ratio':>6} {'QPS':>8} {'recall':>7}")
    engines = {}
    for tier in ("float32", "float16", "int8"):
        idx = MSTGIndex.build(IndexSpec(predicate=Overlaps(), variants=(),
                                        storage_dtype=tier),
                              ds.vectors, ds.lo, ds.hi)
        eng = QueryEngine(idx)
        engines[tier] = eng
        res = eng.search(req)                       # warm the jit cache
        t0 = time.perf_counter()
        for _ in range(3):
            res = eng.search(req)
        dt = (time.perf_counter() - t0) / 3
        sb = idx.storage_bytes()
        print(f"{tier:>8} {sb['scan_bytes']/1e6:8.1f} "
              f"{sb['compression_ratio']:6.2f} {len(req)/dt:8.1f} "
              f"{recall_at_k(np.asarray(res.ids), tids):7.4f}")

    # 2. the rerank_k knob: how wide the exact re-rank looks. k trusts the
    # approximate (quantized) order; the default max(4k, 32) recovers recall
    idx8 = engines["int8"].index
    print("\nrerank_k sweep (int8):")
    for r in (10, 20, 40, 80):
        eng = QueryEngine(idx8, config=EngineConfig(rerank_k=r))
        rec = recall_at_k(np.asarray(eng.search(req).ids), tids)
        print(f"  rerank_k={r:<3d} recall@10={rec:.4f}")

    # 3. quantizing an existing float32 index on the fly (no rebuild): the
    # engine fits codes at construction from the retained float32 corpus
    eng = QueryEngine(engines["float32"].index,
                      config=EngineConfig(storage_dtype="int8"))
    rec = recall_at_k(np.asarray(eng.search(req).ids), tids)
    print(f"\non-the-fly int8 over a float32 index: recall@10={rec:.4f}")

    # 4. persistence: codes/scales travel inside the one .npz artifact
    with tempfile.TemporaryDirectory() as tmp:
        path = idx8.save(f"{tmp}/quant.npz")
        loaded = MSTGIndex.load(path)
        same = np.array_equal(loaded.storage.codes, idx8.storage.codes)
        print(f"saved+loaded int8 artifact: codes bit-identical={same}")


if __name__ == "__main__":
    main()
