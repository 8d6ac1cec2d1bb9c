"""Benchmark of the served, exact range-filtered vector search path.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: makes the
corpus, the index and the traffic from the seed, warms every program the
window can meet (set-up), serves the traffic through the server for
``--seconds``, then checks a sample of the served answers against the
plain reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window), ``device``, ``breakdown`` (``--trace 1``) and
``check`` (each compared number beside its limit).

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result. ``--rehearse N`` runs the cell on whatever JAX finds, at
N corpus rows: a rehearsal of the control flow, never a measurement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import (cellrun, drive, layers, reference, spec,  # noqa: E402
                       trace)

# a traced run serves for at most this long: a profile of the scan holds
# an event per operation per loop iteration, ~150,000 a second
TRACE_SECONDS = 10.0


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", type=int, default=0,
                   help="run on any platform at this many corpus rows")
    p.add_argument("--save-trace", default="",
                   help="with --trace 1, write a summary of the profile and "
                        "its first 20 ms of window as JSON to this file")
    return p.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def device_info(jax, devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


class Compiles:
    """Programs compiled or loaded from the cache, with the time each
    arrived."""

    def __init__(self, jax):
        self.times = []
        from jax._src import dispatch  # the event name JAX records
        self.event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.event:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.times)


def latency_percentiles(win: drive.Window):
    """p50 / p99 in ms over every send due in the window, from its due time
    to its answer; a send refused or never answered counts as answered
    when the run gave up on it (the drain's end)."""
    sends = win.sends_in_window()
    done = win.answered[sends]
    last = np.nanmax(win.answered) if np.isfinite(win.answered).any() \
        else win.t1
    done = np.where(win.rejected[sends] | ~np.isfinite(done), last, done)
    lat = (done - win.due[sends]) * 1e3
    return float(np.percentile(lat, 50)), float(np.percentile(lat, 99))


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec.load_cell(args.workload)
        jax = cellrun.setup_jax()
        devices = cellrun.devices_for(jax, cell, bool(args.rehearse))
    except (OSError, KeyError, ValueError, cellrun.Unfit) as e:
        return fail(str(e))
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    st = cellrun.prepare(cell, args.seed, seconds, devices, args.rehearse)
    compiles = Compiles(jax)
    setup_s = time.perf_counter() - T_START

    spans = layers.HostSpans() if args.trace else None
    if spans:
        prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
        spans.install(st.system)
        jax.profiler.start_trace(prof_dir)
    win = cellrun.serve(st, seconds, mark=spans.mark if spans else None)
    dev = device_info(jax, devices)
    n_compiles = compiles.between(win.t0, win.t1)
    st.system.close()
    st.system = None
    gc.collect()

    smp = cellrun.sample(win, st, args.seed)
    numbers = cellrun.judge(st, smp)
    limits = st.cfg["check"]
    result = {"correct": cellrun.verdict(numbers, smp, limits),
              "attempted": int(win.sends_in_window().size),
              "failed": smp.rejected + smp.unanswered}
    metrics = {}
    if args.trace:
        ctx = layers.context(win, spans, prof_dir, st.corpus, devices, dev,
                             n_compiles)
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if args.save_trace:
            cut = trace.excerpt(ctx.trace, ctx.lo_ns, ctx.lo_ns + 2e7)
            with open(args.save_trace, "w") as f:
                json.dump({"summary": trace.summary(ctx.trace),
                           "window_ns": [ctx.lo_ns, ctx.hi_ns],
                           "excerpt": cut.to_json()}, f)
        dev["busy_s"] = ctx.busy_s
        dev["window_s"] = ctx.window_s
        result["breakdown"] = ctx.breakdown
    else:
        p50, p99 = latency_percentiles(win)
        e2e = {"qps": np.sum(np.isfinite(win.answered)
                             & (win.answered <= win.t1)
                             & ~win.rejected) / win.seconds,
               "p50_ms": p50, "p99_ms": p99,
               "hbm_peak_gb": dev["memory_peak_bytes"] / 1e9,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = dev
    check = reference.check_lines(numbers, limits)
    result["check"] = check
    print(f"bench: setup {setup_s:.3f} s ({st.n_warm} warm-up batches), "
          f"window {win.seconds:.3f} s, {win.steps} steps, "
          f"{len(win.request)} sends, {len(win.ids)} answered, "
          f"{n_compiles} programs compiled or loaded in the window",
          file=sys.stderr)
    for name, c in check.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
