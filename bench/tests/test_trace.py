"""The reduction from a profile to the per-layer numbers."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import trace

FIXTURE = Path(__file__).with_name("fixtures") / "tpu_trace_excerpt.json"
P = "/device:TPU:0"


def small():
    # ops: [0,10) and [5,20) overlap; [30,40); a module spanning both runs
    dev = {P: {"XLA Ops": [("fusion.1", 0, 10), ("gather.2", 5, 20),
                           ("fusion.1", 30, 40)],
               "XLA Modules": [("jit__pruned_search_variant(1)", 0, 20),
                               ("jit_merge_topk(2)", 30, 40)]}}
    host = [("bench.window", 0, 50), ("bench.step", 0, 45),
            ("bench.execute", 2, 25), ("bench.plan", 20, 24),
            ("bench.submit", 45, 48)]
    return trace.Trace(dev, host)


def test_busy_and_gaps():
    t = small()
    assert trace.busy_ns(t, P, 0, 50) == 30
    assert trace.idle_gaps(t, P, 0, 50) == [(20, 30), (40, 50)]
    assert trace.busy_ns(t, P, 8, 35) == 17


def test_matched_by_pattern_and_clipped():
    t = small()
    assert trace.matched_ns(t, P, "XLA Modules", r"_pruned_search_variant",
                            0, 50) == 20
    assert trace.matched_ns(t, P, "XLA Modules", r"_pruned_search_variant",
                            10, 50) == 10
    assert trace.matched_ns(t, P, "XLA Modules", r"nothing", 0, 50) == 0


def test_gaps_go_to_the_innermost_host_span():
    t = small()
    got = dict(trace.gaps_by_host(t, [P], 0, 50))
    # gap [20,30): plan covers 20-24, execute 24-25, step 25-30
    # gap [40,50): step 40-45, submit 45-48, nothing 48-50
    assert got == pytest.approx({"bench.plan": 4e-9, "bench.execute": 1e-9,
                                 "bench.step": 10e-9, "bench.submit": 3e-9,
                                 "host.none": 2e-9})


def test_top_ops_by_time():
    t = small()
    assert trace.top_ops(t, [P], 0, 50)[0] == ["fusion.1", pytest.approx(
        20e-9)]


def timeline(intervals, lo, hi):
    """A 1-ns boolean picture of [lo, hi): the check that the sweep in
    ``trace`` is right, done the slow way."""
    on = np.zeros(int(hi - lo), bool)
    for s, e in intervals:
        a, b = int(max(s, lo) - lo), int(min(e, hi) - lo)
        if b > a:
            on[a:b] = True
    return on


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    """An excerpt of a profile recorded on a TPU v5e while the server ran
    the pruned scan: busy time, the scan program's time and the attributed
    idle time agree with a 1-ns picture of the same events."""
    obj = json.loads(FIXTURE.read_text())
    t = trace.Trace.from_json(obj["excerpt"])
    lo, hi = obj["lo"], obj["hi"]
    ops = [(s, e) for _, s, e in t.device[P]["XLA Ops"]]
    on = timeline(ops, lo, hi)
    assert trace.busy_ns(t, P, lo, hi) == pytest.approx(on.sum(), abs=len(
        ops))
    mods = [(s, e) for n, s, e in t.device[P][trace.MODULE_LINE]
            if "_pruned_search_variant" in n]
    scan = trace.matched_ns(t, P, trace.MODULE_LINE,
                            r"_pruned_search_variant", lo, hi)
    assert scan == pytest.approx(timeline(mods, lo, hi).sum(), abs=len(mods))
    assert 0 < on.sum() < hi - lo
    by_host = trace.gaps_by_host(t, [P], lo, hi)
    assert sum(v for _, v in by_host) == pytest.approx(
        (~on).sum() * 1e-9, abs=len(ops) * 1e-9)


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_every_per_layer_reader_reads_the_recorded_trace():
    """Each per-layer metric of BENCHMARK.json loads by its name and reads
    the recorded excerpt; shares of a roofline or of the window stay
    within 0-100%."""
    from bench.lib import layers, peaks, spec
    obj = json.loads(FIXTURE.read_text())
    t = trace.Trace.from_json(obj["excerpt"])
    lo, hi = obj["lo"], obj["hi"]
    ctx = layers.Context(
        window_s=(hi - lo) * 1e-9, served=16,
        host_s={"step": 0.004, "execute": 0.003, "plan": 0.0002},
        late_ms=np.array([0.0, 1.0]), compiles=0,
        busy_s=trace.busy_ns(t, P, lo, hi) * 1e-9, needed_bytes=2.6e8,
        needed_flops=4e8, peaks=peaks.of("TPU v5 lite"), trace=t,
        planes=[P], lo_ns=lo, hi_ns=hi, breakdown={})
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        value = spec.metric_reader(m["name"])(ctx)
        assert value is not None, m["name"]
        if m["unit"] == "%":
            assert 0.0 <= value <= 100.0, (m["name"], value)
