"""Observability walkthrough: trace spans, explain(), metrics, profiling.

Walks the ``repro.obs`` surface end to end on a small MSTG index:

1. one traced request — ``SearchRequest(trace=True)`` returns a
   ``SearchResult`` carrying a span tree (plan -> route decision -> per-slot
   execution -> merge); ``explain()`` renders it, ``trace.save()`` writes
   Chrome-trace JSON for chrome://tracing or https://ui.perfetto.dev;
2. the same through a 2-shard ``ShardedDeployment`` — the inner engines
   join the outer trace, so one file shows fan-out, per-shard search, and
   the merge schedule;
3. engine-level sampling — ``EngineConfig(trace_sample=0.25)`` traces every
   4th request with no caller opt-in;
4. scoped capture + kernel spans — ``with obs.capture()`` traces any
   block; kernel spans carry the bytes their kernel streams;
5. the metrics registry — counters/histograms every subsystem records into,
   snapshot + Prometheus text (``repro.launch.serve --metrics-port`` serves
   the same over HTTP).

    PYTHONPATH=src python examples/tracing.py
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro import obs
from repro.core import (EngineConfig, IndexSpec, MSTGIndex, Overlaps,
                        QueryEngine, SearchRequest)
from repro.data import make_range_dataset, make_queries


def main():
    n, d = 1200, 32
    ds = make_range_dataset(n=n, d=d, n_queries=8, quantize=128, seed=0)
    spec = IndexSpec(variants=("T", "Tp"), m=12, ef_con=64)
    idx = MSTGIndex.build(spec, ds.vectors, ds.lo, ds.hi)
    engine = QueryEngine(idx)
    qlo, qhi = make_queries(ds, Overlaps().mask, 0.15, seed=2)

    # 1. one traced request: where did this query's time go?
    req = SearchRequest(ds.queries[:4], (qlo[:4], qhi[:4]), Overlaps(), k=10,
                        trace=True)
    res = engine.execute(req)
    print("=== explain(): route report + span tree ===")
    print(res.explain())
    path = res.trace.save("/tmp/repro_trace.json")
    print(f"\nChrome-trace JSON written to {path} "
          "(open in chrome://tracing or ui.perfetto.dev)\n")

    # 2. the same request through a sharded deployment: the shard engines
    # join the request's trace, so one tree covers fan-out + merge
    from repro.distributed import DeploymentSpec, ShardedDeployment
    dep = ShardedDeployment.build(ds.vectors, ds.lo, ds.hi, mesh=None,
                                  spec=DeploymentSpec(n_shards=2, index=spec))
    sres = dep.execute(req)
    print("=== sharded span tree ===")
    print(sres.trace.render())

    # 3. engine-level sampling: no caller opt-in, every 4th request traced
    sampled = QueryEngine(idx, config=EngineConfig(trace_sample=0.25))
    req_off = SearchRequest(ds.queries[:4], (qlo[:4], qhi[:4]), Overlaps())
    traced = [sampled.execute(req_off).trace is not None for _ in range(8)]
    print(f"\ntrace_sample=0.25 over 8 requests -> traced={traced}")

    # 4. scoped capture around arbitrary code; kernel spans carry the bytes
    # their kernel streams by its byte model
    from repro.kernels import ops
    import jax.numpy as jnp
    q = jnp.asarray(ds.queries[:4])
    cand = jnp.asarray(np.stack([ds.vectors[:16]] * 4))
    with obs.capture() as tr:
        ops.gathered_l2(q, cand)
    ksp = tr.trace().roots[0]
    print(f"kernel span: {ksp.name} {ksp.args}")

    # 5. the process metrics registry (the engine recorded into it above)
    snap = obs.get_registry().snapshot()
    print(f"\nmetrics families: {sorted(snap['metrics'])}")
    print("Prometheus exposition (first lines):")
    print("\n".join(obs.get_registry().render_prometheus()
                    .splitlines()[:8]))
    print("\n(serve these over HTTP: python -m repro.launch.serve "
          "--metrics-port 9100)")


if __name__ == "__main__":
    main()
