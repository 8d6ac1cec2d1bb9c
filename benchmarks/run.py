"""Benchmark harness — one module per paper table/figure.

Default mode prints ``name,us_per_call,derived`` CSV for every experiment
(BENCH_QUICK=1 shrinks sizes). ``--smoke`` instead runs the tiny CI lane
(exp1 + kernel bench + planner microbenchmark) and writes BENCH_smoke.json.
``--scale`` runs the sharded recall-QPS pareto lane at n >= 200k (multi-
device via XLA_FLAGS=--xla_force_host_platform_device_count) and writes
BENCH_scale.json.
"""
import argparse
import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI lane; writes a JSON perf artifact")
    ap.add_argument("--scale", action="store_true",
                    help="sharded pareto lane at n >= 200k; writes "
                         "BENCH_scale.json (multi-device when XLA_FLAGS "
                         "forces a host device count)")
    ap.add_argument("--out", default=None,
                    help="output path for --smoke / --scale (defaults: "
                         "BENCH_smoke.json / BENCH_scale.json)")
    ap.add_argument("--scale-n", type=int, default=200_000,
                    help="--scale corpus size (default 200000)")
    ap.add_argument("--graph-n", type=int, default=0,
                    help="--scale graph-lane corpus size (0 = lane off; "
                         "the scheduled CI lane runs 1000000)")
    ap.add_argument("--graph-shards", default="8",
                    help="--scale graph-lane comma-separated shard counts "
                         "(default 8)")
    ap.add_argument("--graph-efs", default="48,96",
                    help="--scale graph-lane comma-separated ef values "
                         "(default 48,96)")
    ap.add_argument("--build-workers", type=int, default=0,
                    help="process-pool width for --scale graph-lane shard "
                         "builds (0 = serial)")
    ap.add_argument("--shards", default="1,2,4,8",
                    help="--scale comma-separated shard counts "
                         "(default 1,2,4,8)")
    ap.add_argument("--mask", default="any_overlap",
                    help="RR predicate for the smoke lane, in any parse_mask "
                         "spelling: 'any_overlap', '1|2|<', '2,4' (single "
                         "digits are the paper's case numbers), or a "
                         "multi-digit raw int mask like '15' "
                         "(default: any_overlap)")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="append a one-line JSON record (keyed by commit) to "
                         "PATH after --smoke, accumulating the bench "
                         "trajectory across runs")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        from repro.core import parse_mask

        from .smoke import run_smoke
        report = run_smoke(out_path=args.out or "BENCH_smoke.json",
                           mask=parse_mask(args.mask),
                           history_path=args.history)
        # per-section failures are isolated inside run_smoke (each records
        # into report["errors"] and the remaining sections still run +
        # land in history); surface them as a non-zero exit at the end so
        # a serving/kernel regression can't silently pass the lane
        errors = report.get("errors", {})
        if errors:
            for sec, msg in errors.items():
                print(f"SMOKE SECTION FAILED: {sec}: {msg}",
                      file=sys.stderr)
            sys.exit(1)
        return

    if args.scale:
        from repro.core import parse_mask

        from .scale import run_scale
        run_scale(out_path=args.out or "BENCH_scale.json", n=args.scale_n,
                  mask=parse_mask(args.mask),
                  shard_counts=tuple(int(s) for s in args.shards.split(",")),
                  history_path=args.history, graph_n=args.graph_n,
                  graph_shards=tuple(int(s)
                                     for s in args.graph_shards.split(",")),
                  graph_efs=tuple(int(e)
                                  for e in args.graph_efs.split(",")),
                  build_workers=args.build_workers)
        return

    from . import (exp1_rrann, exp2_index_cost, exp3_rfann, exp4_ifann,
                   exp5_tsann, exp6_scalability, exp7_selectivity,
                   exp8_distributions, exp9_oracle, exp10_params,
                   exp11_updates, exp12_wavefront, exp13_serving,
                   exp14_obs, exp15_compression, kernel_bench)
    mods = [exp1_rrann, exp2_index_cost, exp3_rfann, exp4_ifann, exp5_tsann,
            exp6_scalability, exp7_selectivity, exp8_distributions,
            exp9_oracle, exp10_params, exp11_updates, exp12_wavefront,
            exp13_serving, exp14_obs, exp15_compression, kernel_bench]
    print("name,us_per_call,derived")
    failed = 0
    for mod in mods:
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{mod.__name__},ERROR,", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
