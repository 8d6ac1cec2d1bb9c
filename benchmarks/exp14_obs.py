"""Exp. 14 — observability overhead + trace-export sanity (PR 7 gate).

Two questions, answered in one artifact (``BENCH_obs.json``):

1. **What does instrumentation cost?** The same cold graph-route search
   the smoke lane times (identical sizes/seeds, best-of-7, selectivity
   cache cleared per call) is run twice — tracing off (the no-op fast path
   every production query takes) and ``SearchRequest(trace=True)``. The
   gated headline ``obs_overhead_pct`` is the **no-op instrumentation
   share** of an untraced request: spans-per-request (counted from the
   traced run) x the microbenchmarked no-op ``obs.span()`` cost, as a
   percentage of the untraced request time — a ratio of two same-box
   measurements, so it stays stable where raw cross-run wall clock does
   not, and it grows if either the span count on the hot path or the
   no-op path cost creeps up (``ci_gate --field obs_overhead_pct
   --direction min``). The traced-ON slowdown is recorded as
   ``trace_on_overhead_pct`` (informational: the traced path deliberately
   blocks on device results per kernel/chunk so spans measure work).

2. **Does the export pipeline still work?** One ``trace=True`` request
   through ``engine_auto`` on a 2-shard :class:`ShardedDeployment` must
   yield Chrome-trace JSON whose spans cover plan, route decision,
   per-shard search, and merge, with ``explain()`` rendering the same —
   the PR's acceptance scenario, re-checked on every scheduled run.

Because the traced-off measurement replicates the smoke lane's
``graph_qps`` row exactly, it is directly comparable against prior
same-platform ``graph_qps`` history records: when one exists,
``traced_off_vs_history`` records the < 5% no-op-overhead budget verdict
against the pre-PR baseline (hard-fail at the 20% band the graph_qps
gate uses — single cross-process samples swing past 5% on shared boxes). ``--history`` appends ``obs_overhead_pct`` (plus
``obs_graph_qps`` — namespaced so smoke's ``graph_qps`` gate never
compares across workloads) to the shared trajectory file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import platform
import time

import numpy as np

from repro import obs
from repro.core import (ANY_OVERLAP, IndexSpec, MSTGIndex, QueryEngine,
                        SearchRequest, intervals as iv)
from repro.data import make_queries, make_range_dataset

from .common import last_timing, time_call

# mirror of the smoke lane's graph_qps row (run_smoke defaults) — the
# traced-off number here must stay comparable with smoke history records
SMOKE_N, SMOKE_D, SMOKE_Q, SMOKE_K, SMOKE_SEL = 800, 32, 16, 10, 0.05

REQUIRED_SPANS = ("sharded_search", "plan", "shard", "fetch", "merge",
                  "route")


def noop_span_ns(iters: int = 200_000) -> float:
    """ns per ``obs.span()`` enter/exit with no tracer active — the cost
    every untraced query pays at each instrumentation point."""
    t0 = time.perf_counter()
    for _ in range(iters):
        with obs.span("noop") as sp:
            sp.set("k", 1)
    return (time.perf_counter() - t0) / iters * 1e9


def trace_export_sanity(ds, k: int = SMOKE_K) -> dict:
    """The acceptance scenario: engine_auto + trace=True on a 2-shard
    host-merge deployment -> valid Chrome JSON covering plan / route /
    per-shard / merge, and explain() rendering the same spans."""
    from repro.distributed import DeploymentSpec, ShardedDeployment
    dep = ShardedDeployment.build(
        ds.vectors, ds.lo, ds.hi, mesh=None,
        spec=DeploymentSpec(n_shards=2,
                            index=IndexSpec(variants=("T", "Tp"), m=8,
                                            ef_con=48)))
    qlo, qhi = make_queries(ds, ANY_OVERLAP, SMOKE_SEL, seed=11)
    res = dep.execute(SearchRequest(ds.queries[:4], (qlo[:4], qhi[:4]),
                                    ANY_OVERLAP, k=k, trace=True))
    out = {"ok": False, "spans": [], "chrome_events": 0}
    if res.trace is None:
        out["error"] = "no trace attached"
        return out
    names = res.trace.span_names()
    out["spans"] = names
    chrome = json.loads(res.trace.to_json())
    events = chrome.get("traceEvents", [])
    out["chrome_events"] = len(events)
    missing = [s for s in REQUIRED_SPANS if s not in names]
    if missing:
        out["error"] = f"missing spans: {missing}"
        return out
    if not events or any(e.get("ph") != "X" for e in events):
        out["error"] = "traceEvents not complete ('X') events"
        return out
    rendered = res.explain()
    if not all(s in rendered for s in ("route:", "trace:", "shard[0]",
                                       "merge")):
        out["error"] = "explain() missing trace breakdown"
        return out
    out["ok"] = True
    return out


def compare_vs_history(history_path: str, platform_str: str,
                       qps_off: float, window: int = 5) -> dict:
    """Traced-off QPS vs the best same-platform smoke ``graph_qps`` of the
    last ``window`` history records — the < 5% budget vs the pre-PR
    baseline. Skipped (not failed) when no comparable record exists."""
    try:
        with open(history_path) as f:
            recs = [json.loads(line) for line in f if line.strip()]
    except FileNotFoundError:
        return {"available": False, "reason": f"no history at {history_path}"}
    prior = [r for r in recs if r.get("graph_qps") is not None
             and r.get("platform") == platform_str]
    if not prior:
        return {"available": False,
                "reason": "no same-platform graph_qps record"}
    base = max(r["graph_qps"] for r in prior[-window:])
    reg = (base - qps_off) / base * 100.0
    return {"available": True, "baseline_qps": base,
            "traced_off_qps": round(qps_off, 1),
            "regression_pct": round(reg, 2),
            "within_5pct": bool(reg < 5.0)}


def run_obs_bench(out_path: str = "BENCH_obs.json",
                  history_path: str = None,
                  baseline_history: str = "BENCH_history.jsonl") -> dict:
    report: dict = {"schema": 1, "unix_time": time.time(),
                    "platform": platform.platform(),
                    "sizes": {"n": SMOKE_N, "d": SMOKE_D,
                              "queries": SMOKE_Q, "k": SMOKE_K,
                              "sel": SMOKE_SEL}}

    ds = make_range_dataset(n=SMOKE_N, d=SMOKE_D, n_queries=SMOKE_Q,
                            quantize=128, dist="uniform", seed=0)
    idx = MSTGIndex(ds.vectors, ds.lo, ds.hi, variants=("T", "Tp", "Tpp"),
                    m=12, ef_con=64)
    eng = QueryEngine(idx)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, SMOKE_SEL, seed=11)
    req_off = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=SMOKE_K,
                            ef=64, route="graph")
    req_on = dataclasses.replace(req_off, trace=True)

    def cold_search(req):
        # identical discipline to the smoke lane's graph_qps row
        eng._sel_cache.clear()
        return eng.search(req)

    dt_off, _ = time_call(cold_search, req_off, repeats=7, best=True,
                          name="obs_traced_off")
    spread_off = last_timing()
    dt_on, res_on = time_call(cold_search, req_on, repeats=7, best=True,
                              name="obs_traced_on")
    # interleave a second off-pass and keep the best: the vs-history budget
    # below compares across processes on a shared box whose wall clock
    # swings well past the 5% budget (see ci.yml's gate-tolerance notes),
    # so a single unlucky pass must not decide it
    dt_off2, _ = time_call(cold_search, req_off, repeats=7, best=True,
                           name="obs_traced_off")
    dt_off = min(dt_off, dt_off2)
    assert res_on.trace is not None, "trace=True returned no trace"
    qps_off = SMOKE_Q / dt_off
    qps_on = SMOKE_Q / dt_on
    report["graph_qps_traced_off"] = round(qps_off, 1)
    report["graph_qps_traced_on"] = round(qps_on, 1)
    report["graph_repeat_ms"] = {"p50": round(spread_off["p50_s"] * 1e3, 2),
                                 "p95": round(spread_off["p95_s"] * 1e3, 2)}
    # informational only: the traced path deliberately blocks on device
    # results per kernel/chunk so spans measure real work, and cross-run
    # wall clock on this class of box swings past any tight budget anyway
    report["trace_on_overhead_pct"] = round((dt_on - dt_off) / dt_off * 100.0,
                                            2)
    noop_ns = noop_span_ns()
    n_spans = len(res_on.trace.span_names())
    report["noop_span_ns"] = round(noop_ns, 1)
    report["trace_spans_recorded"] = n_spans
    # gated headline: the no-op instrumentation share of an untraced
    # request — spans-per-request (counted from the traced run) x the
    # microbenchmarked no-op span cost, as a % of the untraced request
    # time. A ratio of two same-process measurements, so it is stable
    # where raw wall clock is not, and it rises if either the span count
    # on the hot path or the no-op path cost creeps up.
    report["obs_overhead_pct"] = round(
        n_spans * noop_ns / (dt_off * 1e9) * 100.0, 4)

    report["trace_export"] = trace_export_sanity(ds)
    report["traced_off_vs_history"] = compare_vs_history(
        baseline_history, report["platform"], qps_off)

    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path}")
    print(json.dumps({k: report[k] for k in
                      ("graph_qps_traced_off", "graph_qps_traced_on",
                       "obs_overhead_pct", "noop_span_ns")}, indent=2))
    print(f"trace_export ok={report['trace_export']['ok']} "
          f"spans={report['trace_export']['spans']}")
    print(f"vs_history: {json.dumps(report['traced_off_vs_history'])}")

    if history_path:
        record = {
            "commit": os.environ.get("GITHUB_SHA", "local")[:12],
            "unix_time": round(report["unix_time"], 1),
            "platform": report["platform"],
            "mask": iv.mask_name(ANY_OVERLAP),
            "obs_overhead_pct": report["obs_overhead_pct"],
            "obs_graph_qps": report["graph_qps_traced_off"],
            "obs_trace_export_ok": report["trace_export"]["ok"],
        }
        with open(history_path, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")
        print(f"appended {history_path}: {json.dumps(record, sort_keys=True)}")

    if not report["trace_export"]["ok"]:
        raise RuntimeError(
            f"trace export sanity failed: {report['trace_export']}")
    # the < 5% no-op budget verdict is asserted in the artifact
    # (within_5pct); hard-fail only past the same 20% band the graph_qps
    # ci_gate uses — single cross-process samples on a shared box swing
    # past 5% routinely, and the trend is what the gates watch
    vs = report["traced_off_vs_history"]
    if vs.get("available") and vs["regression_pct"] > 20.0:
        raise RuntimeError(
            f"traced-off graph QPS regressed {vs['regression_pct']}% vs "
            f"same-platform baseline {vs['baseline_qps']} "
            f"(no-op budget < 5%, hard-fail band 20%)")
    return report


def run():
    """CSV mode (benchmarks.run default lane): tracing on/off cost."""
    report = run_obs_bench(out_path=os.devnull)
    from .common import emit
    emit("exp14/graph_traced_off",
         1e6 / max(report["graph_qps_traced_off"], 1e-9),
         f"qps={report['graph_qps_traced_off']}")
    emit("exp14/graph_traced_on",
         1e6 / max(report["graph_qps_traced_on"], 1e-9),
         f"qps={report['graph_qps_traced_on']};"
         f"overhead_pct={report['trace_on_overhead_pct']}")
    emit("exp14/noop_span", report["noop_span_ns"] / 1e3,
         f"ns={report['noop_span_ns']}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="alias for the default sizes (the lane is already "
                         "smoke-scale); writes BENCH_obs.json")
    ap.add_argument("--out", default="BENCH_obs.json")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="append obs_overhead_pct/obs_graph_qps JSON line")
    ap.add_argument("--baseline-history", default="BENCH_history.jsonl",
                    metavar="PATH",
                    help="smoke history file for the traced-off <5%% "
                         "vs-baseline assertion (skipped when absent)")
    args = ap.parse_args()
    run_obs_bench(out_path=args.out, history_path=args.history,
                  baseline_history=args.baseline_history)


if __name__ == "__main__":
    main()
