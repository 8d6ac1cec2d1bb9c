"""Readings of the compared numbers for the program and for its control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--out FILE]

For each seed, in one process: the cell's set-up and a window of
``--seconds`` as ``bench/run.py`` runs them, then the numbers that decide
``correct`` for the sampled served answers (the program), and for the
control's answers to the same requests: the reference computed with its
distances contracted in bfloat16x3, TPU's ``Precision.HIGH``, the precision
just below the float32-at-highest that the configuration states. The
limits in the configuration's ``check`` are set between the program's
largest and the control's smallest readings. One JSON line per seed.
Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import cellrun, reference, spec  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rehearse", type=int, default=0)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        jax = cellrun.setup_jax()
        devices = cellrun.devices_for(jax, cell, bool(args.rehearse))
    except cellrun.Unfit as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    out = open(args.out, "a") if args.out else None
    try:
        for seed in [int(x) for x in args.seeds.split(",")]:
            t = time.perf_counter()
            st = cellrun.prepare(cell, seed, args.seconds, devices,
                                 args.rehearse)
            setup = time.perf_counter() - t
            win = cellrun.serve(st, args.seconds)
            st.system.close()
            st.system = None
            gc.collect()
            smp = cellrun.sample(win, st, seed)
            k = st.mix["k"]
            ref = reference.control_or_reference(st.corpus, smp.request,
                                                 st.requests, k, False)
            prog = cellrun.judge(st, smp, ref)
            ctl_ids, ctl_d = reference.control_or_reference(
                st.corpus, smp.request, st.requests, k, True)
            ctl = reference.compare(st.corpus, st.requests, smp.request,
                                    ctl_ids, ctl_d, 0, *ref)
            line = {"workload": cell.name, "seed": seed,
                    "sampled": int(smp.sends.size),
                    "answered": len(win.ids), "setup_s": setup,
                    "program": prog, "control": ctl,
                    "limits": st.cfg["check"]}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
            del st, win, smp
            gc.collect()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
