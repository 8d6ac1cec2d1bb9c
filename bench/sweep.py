"""Find the knee of an open-loop cell: the highest offered rate that the
server delivers with no growing backlog.

    python bench/sweep.py --workload <cell> --rates 50,100,200 \
        --seconds 20 --seed 7 [--out FILE]

Runs the cell's set-up once, then for each rate a window of the cell's
traffic offered at that rate, and prints one JSON line per rate: offered
and delivered rate, p50 / p99 latency from due time, and the backlog (sends
not answered by the window's close). The knee found so is written into the
traffic file as a fixed rate; benchmark runs never sweep.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import cellrun, spec, traffic, warm  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rehearse", type=int, default=0)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        jax = cellrun.setup_jax()
        devices = cellrun.devices_for(jax, cell, bool(args.rehearse))
    except cellrun.Unfit as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    rates = [float(x) for x in args.rates.split(",")]
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  rate_qps=max(rates)))
    st = cellrun.prepare(cell, args.seed, args.seconds, devices,
                         args.rehearse)
    for rate in rates:
        mix = dict(st.mix, rate_qps=rate)
        st = dataclasses.replace(
            st, mix=mix, requests=traffic.make_requests(
                mix, st.corpus, args.seed, args.seconds))
        warm.warm(st.system, st.requests, st.corpus.queries, mix)
        win = cellrun.serve(st, args.seconds)
        sends = win.sends_in_window()
        done = win.answered[sends]
        lat = (done - win.due[sends]) * 1e3
        inwin = np.isfinite(done) & (done <= win.t1)
        line = {"workload": cell.name, "rate_qps": rate,
                "sends": int(sends.size),
                "delivered_qps": float(inwin.sum() / win.seconds),
                "backlog_at_close": int((~inwin).sum()),
                "p50_ms": float(np.nanpercentile(lat, 50)),
                "p99_ms": float(np.nanpercentile(lat, 99)),
                "steps": win.steps}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
