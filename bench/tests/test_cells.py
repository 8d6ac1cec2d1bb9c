"""Rehearsals of every cell at a tiny corpus on the CPU (``--rehearse``),
and the refusals: no TPU, no program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 11


def run(*args, cwd=ROOT, rehearse=True):
    cmd = [sys.executable, "bench/run.py", *args]
    if rehearse:
        cmd += ["--rehearse", "3000"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=900)


def metric_names(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace):
    p = run("--workload", cell, "--seed", str(SEED), "--seconds", "2",
            "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, p.stderr[-3000:]
    assert list(res)[-1] == "check"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    if trace:
        # off the chip the profile has no device: only host readings
        assert set(res["metrics"]) <= metric_names("per_layer", cell)
        assert any(k.startswith("compiles_in_window") for k in res["metrics"])
        assert res["metrics"][next(k for k in res["metrics"] if
                                   k.startswith("compiles_in_window"))][
            "value"] == 0
    else:
        assert set(res["metrics"]) == metric_names("end_to_end", cell)
        assert all(m["value"] >= 0 for m in res["metrics"].values())
    tail = p.stderr.strip().splitlines()[-len(res["check"]):]
    assert all(line.startswith("check ") for line in tail)


def test_no_tpu_no_result():
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            rehearse=False)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
