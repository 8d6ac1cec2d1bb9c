"""chip_smoke.py at tiny n on the CPU: every correctness check passes, and
the record stays ``ok: false`` because the platform is not a TPU."""
import functools
import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_one_chip_phases_pass_on_cpu():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    rec = chip_smoke.run(["--n", "1500"])
    assert rec["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert rec["failed"] == ["platform/tpu"]
    assert rec["ok"] is False


def test_four_chips_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "chip_smoke.py", "--four-chips",
                        "--n", "2000"], capture_output=True, text=True,
                       cwd=ROOT, env=env, timeout=600)
    assert r.returncode == 1, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["device"]["count"] == 4
    assert rec["failed"] == ["platform/tpu"], r.stdout
    assert rec["ok"] is False


def test_build_pool_workers_stay_off_the_accelerator(monkeypatch):
    """A spawned build worker must never reach for the chip the parent
    holds: the pool pins its JAX to the CPU whatever the parent's env."""
    from repro.core.parallel import run_build_pool
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    out = run_build_pool(functools.partial(os.getenv, "JAX_PLATFORMS"),
                         ["unset", "unset"], workers=2)
    assert out == ["cpu", "cpu"]
