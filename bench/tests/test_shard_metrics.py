"""The sharded cell's readers on a made-up trace of two chips."""
import numpy as np
import pytest

from bench.lib import layers, peaks, trace

P0, P1 = "/device:TPU:0", "/device:TPU:1"


def ctx_of(ops0, ops1, lo=0.0, hi=100.0, scan=True):
    def plane(ops):
        mods = [("jit__pruned_search_variant", s, e) for s, e in ops] \
            if scan else []
        return {"XLA Ops": [("op", s, e) for s, e in ops],
                trace.MODULE_LINE: mods}
    t = trace.Trace({P0: plane(ops0), P1: plane(ops1)}, [])
    busy = np.mean([trace.busy_ns(t, p, lo, hi) for p in (P0, P1)]) * 1e-9
    return layers.Context(
        window_s=(hi - lo) * 1e-9, served=4, host_s={}, late_ms=np.zeros(0),
        compiles=0, busy_s=busy, needed_bytes=8.19e3, needed_flops=0.0,
        peaks=peaks.of("TPU v5 lite"), trace=t, planes=[P0, P1], lo_ns=lo,
        hi_ns=hi, breakdown={})


@pytest.mark.parametrize("ops0, ops1, want", [
    ([(0, 40)], [(40, 80)], 1.0),            # the chips take turns
    ([(0, 80)], [(0, 80)], 2.0),             # both busy together
    ([(0, 60)], [(20, 80)], 1.5),            # 120 ns of work in 80 ns
    ([(-50, 30), (90, 150)], [(0, 30)], 70 / 40),   # clipped to the window
])
def test_shard_parallelism(ops0, ops1, want):
    from bench.metrics import shard_parallelism
    assert shard_parallelism.read(ctx_of(ops0, ops1)) == pytest.approx(want)


def test_shard_parallelism_reads_nothing_on_an_idle_device():
    from bench.metrics import shard_parallelism
    assert shard_parallelism.read(ctx_of([], [])) is None


def test_shard_scan_roofline_divides_the_work_over_the_chips():
    """8,190 bytes need 10 ns at 819 GB/s; over two chips 5 ns each, and
    each chip's scan ran 20 ns: 25%."""
    from bench.metrics import scan_roofline, shard_scan_roofline
    ctx = ctx_of([(0, 20)], [(50, 70)])
    assert shard_scan_roofline.read(ctx) == pytest.approx(25.0)
    assert scan_roofline.read(ctx) == pytest.approx(50.0)
    assert shard_scan_roofline.read(ctx_of([(0, 20)], [], scan=False)) \
        is None


def test_shard_device_roofline_divides_the_work_over_the_chips():
    """10 ns of needed work over two chips is 5 ns each; the chips were
    busy 20 and 30 ns, 25 ns on average: 20%."""
    from bench.metrics import device_roofline, shard_device_roofline
    ctx = ctx_of([(0, 20)], [(50, 80)])
    assert shard_device_roofline.read(ctx) == pytest.approx(20.0)
    assert device_roofline.read(ctx) == pytest.approx(40.0)
    assert shard_device_roofline.read(ctx_of([], [])) is None
