"""End-to-end system tests: the examples run, the dry-run pipeline works on a
small subprocess mesh, plan->search->serve composes."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, timeout=900, env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    if env_extra:
        env.update(env_extra)
    return subprocess.run(args, capture_output=True, text=True,
                          timeout=timeout, cwd=ROOT, env=env)


@pytest.mark.slow
def test_example_quickstart():
    r = _run([sys.executable, "examples/quickstart.py"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "recall@10" in r.stdout


@pytest.mark.slow
def test_example_train_lm():
    r = _run([sys.executable, "examples/train_lm.py", "--arch", "olmo-1b",
              "--steps", "12", "--batch", "2", "--seq", "64"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "final loss" in r.stdout


@pytest.mark.slow
def test_example_distributed_serving():
    r = _run([sys.executable, "examples/distributed_serving.py"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "tournament" in r.stdout


@pytest.mark.slow
def test_dryrun_pipeline_small_mesh():
    """The dry-run machinery end to end on an 8-device placeholder mesh
    (the 512-device production run is a launch artifact, exercised by
    `python -m repro.launch.dryrun`; its cell results live in artifacts/)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import numpy as np
        import jax
        from repro.configs import get_smoke_config, TRAIN_4K, DECODE_32K
        import dataclasses
        from repro.launch.steps import ArchRunner
        from repro.launch.dryrun import collective_bytes
        from repro.launch.mesh import make_mesh
        from repro.configs.base import ShapeConfig

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_smoke_config("olmo-1b")
        shape = ShapeConfig("t", 64, 8, "train")
        runner = ArchRunner(cfg, mesh)
        b = runner.train_bundle(shape)
        with mesh:
            c = jax.jit(b.fn, in_shardings=b.in_shardings,
                        out_shardings=b.out_shardings,
                        donate_argnums=b.donate).lower(*b.args).compile()
        ca = c.cost_analysis()
        assert ca["flops"] > 0
        colls, wire, counts = collective_bytes(c.as_text(), 8)
        assert sum(counts.values()) > 0, "expected collectives on a 3-axis mesh"
        shape = ShapeConfig("d", 64, 8, "decode")
        b = runner.decode_bundle(shape)
        with mesh:
            c = jax.jit(b.fn, in_shardings=b.in_shardings,
                        donate_argnums=b.donate).lower(*b.args).compile()
        print("DRYRUN-PIPELINE-OK")
    """)
    r = _run([sys.executable, "-c", prog])
    assert "DRYRUN-PIPELINE-OK" in r.stdout, r.stdout + r.stderr


def test_production_dryrun_artifacts_exist_and_clean():
    """The committed 512-device dry-run artifacts must cover all 40 cells on
    both meshes with no errors (33 ok + 7 documented skips per mesh)."""
    import json
    adir = os.path.join(ROOT, "artifacts", "dryrun")
    if not os.path.isdir(adir):
        pytest.skip("dry-run artifacts not generated yet")
    cells = [f for f in os.listdir(adir)
             if f.endswith(".json") and not f.startswith("mstg-flat-serve")]
    assert len(cells) == 80, f"expected 80 cell artifacts, got {len(cells)}"
    status = {"ok": 0, "skipped": 0, "error": 0}
    for f in cells:
        with open(os.path.join(adir, f)) as fh:
            rec = json.load(fh)
        status[rec["status"]] = status.get(rec["status"], 0) + 1
    assert status["error"] == 0, status
    assert status["ok"] == 66 and status["skipped"] == 14, status
