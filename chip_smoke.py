"""Drive the served MSTG path once on a TPU and check it against brute force.

    python chip_smoke.py                # one chip: MSTGIndex.build -> QueryEngine
                                        # -> AsyncRetrievalServer
    python chip_smoke.py --four-chips   # four chips: ShardedDeployment only
    JAX_PLATFORMS=cpu python chip_smoke.py --n 4000     # CPU rehearsal

Data comes from ``repro.data.make_range_dataset`` and ``--seed``: d=128 (the
SIFT shape of ANN-Benchmarks), 256 queries, 128 distinct range endpoints.
The index serves ``ANY_OVERLAP`` and each of its four atoms, which takes
all three MSTG variants (``QueryContaining`` alone plans onto Tpp), built
with the coarse candidate stage in a two-worker process pool. Every answer is compared with
``brute_force_topk`` + ``eval_predicate`` on the index's own arrays: the flat
and pruned routes must return the reference ids (up to distance ties), the
graph route and the int8 tier must reach a recall@10 floor, and the server
must answer every ticket.

The last line of stdout is one JSON object, ``{"ok": ..., "device":
{"platform", "kind", "count"}, "failed": [...]}``. ``ok`` is true, and the
exit code 0, only on a TPU with every check passed. On any other platform
the script exits 1 at once unless ``--n`` asks for a rehearsal size; then
every phase still runs and the line says ``"ok": false``. A phase that
raises ends the script with a traceback and no JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.core import (ANY_OVERLAP, EngineConfig, IndexSpec,  # noqa: E402
                        LeftOverlap, MSTGIndex, Predicate, QueryContained,
                        QueryContaining, QueryEngine, RightOverlap,
                        SearchRequest, Served, eval_predicate)
from repro.data import (brute_force_topk, make_queries,  # noqa: E402
                        make_range_dataset, recall_at_k)

D, N_QUERIES, K, EF = 128, 256, 10, 64
SELECTIVITY = 0.1             # target fraction of the corpus each query keeps
N_FULL = 200_000
# One v5e cannot hold the index at N_FULL: the dense (Lv, n, S) adjacency
# slabs grow with n through S, the widest vertex's edge count.
N_ONE_CHIP = 50_000
N_CUT_REASON = ("the dense (Lv, n, S) adjacency slabs take 34 GB for variants "
                "T and Tp alone at n=200,000 (S=941/842), more than one v5e's "
                "16 GB of HBM, and that build ran 1430 s on an 8-core host; "
                "at n=50,000 all three variants take 8.1 GB (S=416/737/541)")
# Recall@10 floors: the CPU rehearsal's lowest per-mask recall at n=50,000
# with the TPU fanout max(1, min(8, EF // 16)) = 4, minus 0.02 (graph:
# 0.4309 for LeftOverlap; int8 flat: 1.0 for every mask).
GRAPH_FLOOR = 0.41
INT8_FLOOR = 0.98
# An exact route may swap two neighbours whose float64 distances agree to
# this relative tolerance: float32 rounding cannot order them.
TIE_REL = 1e-6

MASKS = {"QueryContained": QueryContained(),
         "QueryContaining": QueryContaining(),
         "LeftOverlap": LeftOverlap(),
         "RightOverlap": RightOverlap(),
         "ANY_OVERLAP": Predicate(ANY_OVERLAP)}


class Checks:
    """Named pass/fail verdicts, printed as they are made."""

    def __init__(self):
        self.failed: list = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'pass' if ok else 'FAIL'} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def exact_misses(ids, ref_ids, vectors, lo, hi, queries, qlo, qhi,
                 mask) -> int:
    """Queries whose ids differ from the reference's other than by
    reordering neighbours tied within ``TIE_REL`` (float64 distances)."""
    bad = 0
    for q in np.flatnonzero((np.asarray(ids) != ref_ids).any(axis=1)):
        got, want = ids[q][ids[q] >= 0], ref_ids[q][ref_ids[q] >= 0]
        if (got.size != want.size or np.unique(got).size != got.size
                or not np.all(eval_predicate(mask, lo[got], hi[got],
                                             qlo[q], qhi[q]))):
            bad += 1
            continue
        x = queries[q].astype(np.float64)
        dg = np.sort(((vectors[got].astype(np.float64) - x) ** 2).sum(1))
        dw = np.sort(((vectors[want].astype(np.float64) - x) ** 2).sum(1))
        bad += int(not np.all(np.abs(dg - dw) <= TIE_REL * dw))
    return bad


def timed(label: str, fn):
    """Run ``fn`` once and print its wall time, compile included."""
    t0 = time.perf_counter()
    out = fn()
    print(f"{label}: {time.perf_counter() - t0:.3f} s (one unwarmed call, "
          "compile included; not a benchmark)", flush=True)
    return out


def make_workload(n: int, seed: int):
    """Corpus, queries, per-mask query ranges and the brute-force answers."""
    ds = make_range_dataset(n=n, d=D, n_queries=N_QUERIES, quantize=128,
                            seed=seed)
    ranges = {name: make_queries(ds, p.mask, SELECTIVITY, seed=seed + 1 + i)
              for i, (name, p) in enumerate(MASKS.items())}
    return ds, ranges


def reference(idx, queries, ranges) -> dict:
    return {name: brute_force_topk(idx.vectors, idx.lo, idx.hi, queries,
                                   *ranges[name], MASKS[name].mask, K)[0]
            for name in MASKS}


def build_index(ds, spec: IndexSpec, workers: int) -> MSTGIndex:
    t0 = time.perf_counter()
    idx = MSTGIndex.build(spec, ds.vectors, ds.lo, ds.hi, workers=workers)
    slabs = {v: tuple(fv.nbr.shape) for v, fv in idx.variants.items()}
    print(f"build: n={ds.n} {time.perf_counter() - t0:.1f} s, "
          f"workers={idx.build_workers}, per variant "
          f"{ {v: round(s, 1) for v, s in idx.build_seconds.items()} } s, "
          f"(Lv, n, S) slabs {slabs}, graph {idx.index_bytes() / 1e9:.3f} GB",
          flush=True)
    return idx


def check_routes(checks: Checks, tag: str, engine, idx, queries, ranges, ref,
                 route: str, floor=None):
    """One request per mask on a pinned route: exact ids (``floor`` None)
    or recall@10 >= ``floor``."""
    for i, (name, pred) in enumerate(MASKS.items()):
        qlo, qhi = ranges[name]
        req = SearchRequest(queries, (qlo, qhi), pred, k=K, ef=EF,
                            route=route)
        res = (timed(f"{tag} first call", lambda: engine.execute(req))
               if i == 0 else engine.execute(req))
        ids = np.asarray(res.ids)
        if floor is None:
            bad = exact_misses(ids, ref[name], idx.vectors, idx.lo, idx.hi,
                               queries, qlo, qhi, pred.mask)
            checks(f"{tag}/{name}", bad == 0,
                   f"queries off the reference={bad}/{len(queries)} "
                   f"identical={np.mean((ids == ref[name]).all(1)):.4f}")
        else:
            r = recall_at_k(ids, ref[name])
            checks(f"{tag}/{name}", r >= floor,
                   f"recall@10={r:.4f} floor={floor:.4f}")


def serve(checks: Checks, tag: str, engine, queries, ranges, ref,
          route=None):
    """Every mask's queries through AsyncRetrievalServer; every ticket must
    come back Served, undegraded, at the graph recall floor."""
    from repro.serving import AsyncRetrievalServer
    server = AsyncRetrievalServer(
        engine, lambda items: queries[np.asarray(items)], k=K, ef=EF,
        route=route)
    t0 = time.perf_counter()
    for name, pred in MASKS.items():
        qlo, qhi = ranges[name]
        tickets = [server.submit(i, qlo[i], qhi[i], pred)
                   for i in range(len(queries))]
        out = server.run_until_idle()
        served = [out.get(t) for t in tickets]
        ok = all(isinstance(s, Served) and not s.degraded for s in served)
        ids = np.stack([s.hit.ids if ok else np.full(K, -1) for s in served])
        r = recall_at_k(ids, ref[name])
        checks(f"{tag}/{name}", ok and r >= GRAPH_FLOOR,
               f"answered={sum(isinstance(s, Served) for s in served)}/"
               f"{len(tickets)} recall@10={r:.4f} floor={GRAPH_FLOOR:.4f}")
    snap = server.snapshot()
    shed = sum(snap["shed"].values())
    print(f"{tag}: {time.perf_counter() - t0:.1f} s for "
          f"{len(MASKS) * len(queries)} tickets (compile included; not a "
          f"benchmark), routes={engine.route_counts}", flush=True)
    checks(f"{tag}/no_shed_or_degraded",
           shed == 0 and snap["degraded"] == 0,
           f"shed={shed} degraded={snap['degraded']}")


SPEC = IndexSpec(predicate=Predicate(ANY_OVERLAP), variants=("T", "Tp", "Tpp"),
                 candidate_stage="coarse")


def one_chip(checks: Checks, n: int, seed: int) -> None:
    ds, ranges = make_workload(n, seed)
    idx = build_index(ds, SPEC, workers=2)
    checks("build/pool", idx.build_workers == 2,
           f"build_workers={idx.build_workers} (a serial fallback fails)")
    queries = ds.queries
    ref = reference(idx, queries, ranges)
    on_tpu = jax.default_backend() == "tpu"

    eng = QueryEngine(idx)                                 # default config
    check_routes(checks, "flat", eng, idx, queries, ranges, ref, "flat")
    check_routes(checks, "pruned", eng, idx, queries, ranges, ref, "pruned")
    check_routes(checks, "graph", eng, idx, queries, ranges, ref, "graph",
                 floor=GRAPH_FLOOR)
    home = jax.devices()[0]
    for what, arr in (("corpus", eng.corpus),
                      ("graph slab T.nbr", eng.graph_dev("T").nbr)):
        checks(f"resident/{what}", arr.devices() == {home},
               f"on {sorted(map(str, arr.devices()))}")
    serve(checks, "served", eng, queries, ranges, ref)
    serve(checks, "served_graph", eng, queries, ranges, ref, route="graph")

    q8 = QueryEngine(idx, config=EngineConfig(storage_dtype="int8"))
    check_routes(checks, "int8_flat", q8, idx, queries, ranges, ref, "flat",
                 floor=INT8_FLOOR)

    kern = QueryEngine(idx, config=EngineConfig(use_kernel=True))
    check_routes(checks, "kernel_flat", kern, idx, queries, ranges, ref,
                 "flat")
    k8 = QueryEngine(idx, config=EngineConfig(use_kernel=True,
                                              storage_dtype="int8"))
    check_routes(checks, "kernel_int8_flat", k8, idx, queries, ranges, ref,
                 "flat", floor=INT8_FLOOR)
    # the fused wavefront kernel holds the whole table in VMEM: on a TPU the
    # engine must refuse it; elsewhere (interpret mode) it must match jnp
    sub = slice(0, 8)
    qlo, qhi = (r[sub] for r in ranges["ANY_OVERLAP"])
    req = SearchRequest(queries[sub], (qlo, qhi), ANY_OVERLAP, k=K, ef=EF,
                        route="graph")
    if on_tpu:
        try:
            kern.execute(req)
            refused = False
        except NotImplementedError as e:
            refused = True
            print(f"kernel_graph refused: {e}", flush=True)
        checks("kernel_graph/refused_on_tpu", refused)
    else:
        same = np.array_equal(kern.execute(req).ids, eng.execute(req).ids)
        checks("kernel_graph/matches_jnp", same)

    stats = home.memory_stats() or {}
    print(f"device memory: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')} "
          f"bytes_in_use={stats.get('bytes_in_use', 'not reported')}",
          flush=True)


def four_chips(checks: Checks, n: int, seed: int) -> None:
    from repro.distributed import DeploymentSpec, ShardedDeployment
    from repro.launch.mesh import make_mesh
    devs = jax.devices()
    if not checks("mesh/four_devices", len(devs) == 4, f"devices={len(devs)}"):
        return
    mesh = make_mesh((4,), ("data",))
    ds, ranges = make_workload(n, seed)
    # the one-engine answer: a scan-only index serves the exact routes
    one = QueryEngine(MSTGIndex.build(
        IndexSpec(variants=SPEC.variants, builder="scan"), ds.vectors, ds.lo,
        ds.hi))
    idx = one.index
    queries = ds.queries
    ref = reference(idx, queries, ranges)
    t0 = time.perf_counter()
    dep = ShardedDeployment.build(
        ds.vectors, ds.lo, ds.hi, mesh=mesh,
        spec=DeploymentSpec(n_shards=4, index=SPEC, build_workers=4))
    print(f"sharded build: n={n} {time.perf_counter() - t0:.1f} s "
          f"report={dep.build_report}", flush=True)
    checks("sharded/build_pool", dep.build_report["pool_size"] == 4,
           f"pool_size={dep.build_report['pool_size']}")
    # each target is stood up right before its requests
    for tag, make, route in (
            ("sharded_pruned", lambda: dep, "pruned"),
            ("sharded_graph", lambda: dep, "graph"),
            ("fused_flat", lambda: ShardedDeployment.flat(
                ds.vectors, ds.lo, ds.hi, mesh=mesh,
                spec=DeploymentSpec(n_shards=4)), None)):
        target = make()
        for i, (name, pred) in enumerate(MASKS.items()):
            qlo, qhi = ranges[name]
            req = SearchRequest(queries, (qlo, qhi), pred, k=K, ef=EF,
                                route=route)
            res = (timed(f"{tag} first call", lambda: target.execute(req))
                   if i == 0 else target.execute(req))
            ids = np.asarray(res.ids)
            whole = res.report.missing_shards == () and not res.degraded
            if route == "graph":
                r = recall_at_k(ids, ref[name])
                checks(f"{tag}/{name}", whole and r >= GRAPH_FLOOR,
                       f"recall@10={r:.4f} floor={GRAPH_FLOOR:.4f} "
                       f"missing={res.report.missing_shards}")
                continue
            one_ids = np.asarray(one.execute(
                SearchRequest(queries, (qlo, qhi), pred, k=K,
                              route=route or "flat")).ids)
            args = (idx.vectors, idx.lo, idx.hi, queries, qlo, qhi,
                    pred.mask)
            bad_ref = exact_misses(ids, ref[name], *args)
            bad_one = exact_misses(ids, one_ids, *args)
            checks(f"{tag}/{name}", whole and bad_ref == bad_one == 0,
                   f"off brute force={bad_ref} off one engine={bad_one} "
                   f"identical to one engine="
                   f"{np.mean((ids == one_ids).all(1)):.4f} "
                   f"missing={res.report.missing_shards}")
    homes = [s.engine.corpus.devices() for s in dep.shards]
    checks("sharded/one_device_per_shard",
           homes == [{d} for d in mesh.devices.flat],
           f"shard corpora on {[sorted(map(str, h)) for h in homes]}")
    for d in devs:
        st = d.memory_stats() or {}
        print(f"device {d}: bytes_in_use="
              f"{st.get('bytes_in_use', 'not reported')} peak_bytes_in_use="
              f"{st.get('peak_bytes_in_use', 'not reported')}", flush=True)


def run(argv=None) -> dict:
    """Parse ``argv``, run the phases, and return the final JSON record."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ShardedDeployment phase")
    ap.add_argument("--n", type=int, default=None,
                    help=f"corpus size (default {N_ONE_CHIP}, on a TPU "
                         "only; off the chip give a small n to rehearse)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"device_count={device['count']}", flush=True)
    if args.n is None:
        if device["platform"] != "tpu":
            # the full-size build takes minutes and tens of GB of host
            # memory; off the chip it would prove nothing
            raise SystemExit(f"no TPU found (platform={device['platform']}):"
                             " the full-size run needs the chip; pass --n "
                             "to rehearse at a small size")
        args.n = N_ONE_CHIP
    if args.n < N_FULL:
        print(f"n cut from {N_FULL} to {args.n}: {N_CUT_REASON}", flush=True)
    checks = Checks()
    (four_chips if args.four_chips else one_chip)(checks, args.n, args.seed)
    checks("platform/tpu", device["platform"] == "tpu",
           f"platform={device['platform']}")
    return {"ok": not checks.failed, "device": device,
            "failed": checks.failed}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    record = run()
    print(json.dumps(record), flush=True)
    sys.exit(0 if record["ok"] else 1)
