"""Observability layer (repro.obs): metrics registry round-trip, trace-span
nesting + Chrome export, the no-op fast path, explain() rendering across the
mask x route grid, and the PR's sharded-deployment acceptance scenario."""
import dataclasses
import json
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.core import (ANY_OVERLAP, EngineConfig, QueryEngine,
                        SearchRequest, intervals as iv)
from repro.obs.metrics import MetricsRegistry, StreamingHistogram
from repro.obs.trace import Tracer
from repro.data import make_queries


def _req(ds, qlo, qhi, mask=ANY_OVERLAP, **kw):
    return SearchRequest(ds.queries, (qlo, qhi), mask, k=5, ef=48, **kw)


class FakeClock:
    """Deterministic clock: each read advances 1 ms."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


# ---- metrics registry ------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests", labels=("route",))
    c.inc(route="graph")
    c.inc(2.0, route="graph")
    c.labels(route="flat").inc()
    assert c.value(route="graph") == 3.0
    assert c.value(route="flat") == 1.0
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.inc(-2)
    assert g.value() == 5.0
    h = reg.histogram("lat_ms", "latency", labels=("op",))
    for v in (1.0, 2.0, 100.0):
        h.observe(v, op="search")
    assert h.labels(op="search").count == 3
    assert h.percentile(50, op="search") >= 1.0


def test_registry_get_or_create_and_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x", "help", labels=("route",))
    assert reg.counter("x", labels=("route",)) is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")
    with pytest.raises(ValueError, match="labels"):
        reg.counter("x", labels=("shard",))
    with pytest.raises(ValueError, match="expected labels"):
        a.inc(shard="0")


def test_snapshot_round_trip():
    reg = MetricsRegistry()
    reg.counter("reqs", "total", labels=("route",)).inc(5, route="graph")
    reg.gauge("inflight", "rows").set(12.5)
    h = reg.histogram("lat_ms", "latency", labels=("op",), lo_ms=0.1,
                      hi_ms=1e3, bins=32)
    for v in (0.5, 3.0, 40.0, 900.0, 5e4):   # last clamps to edge bin
        h.observe(v, op="tick")
    snap = reg.snapshot()
    json.dumps(snap)                          # JSON-stable
    assert snap["schema"] == 1
    reg2 = MetricsRegistry.from_snapshot(snap)
    assert reg2.snapshot() == snap            # bit-for-bit round-trip
    assert reg2.counter("reqs", labels=("route",)).value(route="graph") == 5
    h2 = reg2.get("lat_ms").labels(op="tick")
    assert h2.count == 5 and h2.percentile(95) == h.percentile(95, op="tick")


def test_snapshot_rejects_unknown_schema():
    with pytest.raises(ValueError, match="schema"):
        MetricsRegistry.from_snapshot({"schema": 99, "metrics": {}})


def test_render_prometheus():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests", labels=("route",)).inc(3,
                                                                 route="graph")
    h = reg.histogram("lat_ms", "latency", lo_ms=1.0, hi_ms=100.0, bins=8)
    h.observe(2.0)
    h.observe(50.0)
    text = reg.render_prometheus()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{route="graph"} 3' in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_count 2" in text
    # cumulative bucket counts never decrease
    buckets = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
               if line.startswith("lat_ms_bucket")]
    assert buckets == sorted(buckets)


def test_metrics_http_server():
    reg = MetricsRegistry()
    reg.counter("pings", "scrapes").inc(4)
    server = obs.start_metrics_server(0, registry=reg)
    try:
        host, port = server.server_address[:2]
        text = urllib.request.urlopen(
            f"http://{host}:{port}/metrics").read().decode()
        assert "pings 4" in text
        snap = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/metrics.json").read().decode())
        assert MetricsRegistry.from_snapshot(snap).counter(
            "pings").value() == 4
    finally:
        server.shutdown()


def test_streaming_histogram_compat_reexport():
    # StreamingHistogram moved to repro.obs (PR 7); the serving import path
    # must keep resolving to the same class
    from repro.serving.scheduler import StreamingHistogram as Compat
    assert Compat is StreamingHistogram


# ---- trace spans -----------------------------------------------------------

def test_span_nesting_and_walk():
    with obs.capture(clock=FakeClock()) as tr:
        with obs.span("outer") as o:
            o.set("Q", 4)
            with obs.span("inner_a"):
                pass
            with obs.span("inner_b"):
                with obs.span("leaf"):
                    pass
    trace = tr.trace()
    assert trace.span_names() == ["outer", "inner_a", "inner_b", "leaf"]
    assert [d for _, d in trace.walk()] == [0, 1, 1, 2]
    assert len(trace) == 4


def test_chrome_export_golden():
    tracer = Tracer(clock=FakeClock())           # t0 = 1 ms
    a = tracer.span("a")                         # start 2 ms
    b = tracer.span("b").set("k", 1)             # start 3 ms
    b.stop()                                     # stop 4 ms
    a.stop()                                     # stop 5 ms
    chrome = tracer.trace().to_chrome()
    assert chrome == {
        "displayTimeUnit": "ms",
        "traceEvents": [
            {"name": "a", "cat": "repro", "ph": "X", "ts": 1000.0,
             "dur": 3000.0, "pid": 0, "tid": 0, "args": {}},
            {"name": "b", "cat": "repro", "ph": "X", "ts": 2000.0,
             "dur": 1000.0, "pid": 0, "tid": 0, "args": {"k": 1}},
        ],
    }


def test_out_of_order_stop_unwinds():
    tracer = Tracer(clock=FakeClock())
    outer = tracer.span("outer")
    inner = tracer.span("inner")
    outer.stop()                  # stops inner too (explicit-region contract)
    assert inner.t_stop is not None
    assert tracer._stack == []
    tracer.span("next").stop()    # new span is a fresh root, not a child
    assert [sp.name for sp in tracer.roots] == ["outer", "next"]


def test_noop_fast_path():
    assert not obs.tracing()
    sp = obs.span("anything")
    assert sp is obs.NULL_SPAN                  # singleton, no allocation
    assert sp.set("k", 1) is sp and sp.stop() is sp
    with obs.span("ctx") as c:
        assert c is obs.NULL_SPAN
    # overhead smoke: the disabled path must stay sub-10us per span (it is
    # one thread-local read and one is_enabled() call; the bound is lenient
    # for noisy CI boxes)
    import time
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("noop") as s:
            s.set("k", 1)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 10e-6, f"no-op span path cost {per_call * 1e6:.2f} us"


def test_begin_end_request_trace_nesting():
    t = obs.begin_request_trace()
    assert t is not None and obs.tracing()
    assert obs.begin_request_trace() is None     # inner layer joins, not owns
    assert obs.end_request_trace(None) is None   # inner passthrough
    obs.span("work").stop()
    trace = obs.end_request_trace(t)
    assert not obs.tracing()
    assert trace.span_names() == ["work"]


# ---- engine integration ----------------------------------------------------

def test_engine_trace_on_request(small_ds, built_index):
    ds = small_ds
    eng = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    res = eng.search(_req(ds, qlo, qhi, trace=True))
    assert res.trace is not None
    names = res.trace.span_names()
    assert names[0] == "search"
    assert "route" in names and "plan" in names
    json.loads(res.trace.to_json())              # valid Chrome JSON
    # default path stays untraced and leaves no tracer behind
    res_off = eng.search(_req(ds, qlo, qhi))
    assert res_off.trace is None and not obs.tracing()
    np.testing.assert_array_equal(res.ids, res_off.ids)


def test_engine_trace_sample(small_ds, built_index):
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    eng = QueryEngine(built_index, config=EngineConfig(trace_sample=0.5))
    traced = [eng.search(_req(ds, qlo, qhi)).trace is not None
              for _ in range(4)]
    assert traced == [False, True, False, True]
    with pytest.raises(ValueError, match="trace_sample"):
        EngineConfig(trace_sample=1.5)


def test_engine_metrics_recorded(small_ds, built_index):
    ds = small_ds
    eng = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    reqs = obs.get_registry().counter("engine_requests_total",
                                      labels=("route",))
    lat = obs.get_registry().get("engine_search_ms")
    before = reqs.value(route="pruned")
    before_n = lat.labels(route="pruned").count
    eng.search(_req(ds, qlo, qhi, route="pruned"))
    assert reqs.value(route="pruned") == before + 1
    assert lat.labels(route="pruned").count == before_n + 1


def test_explain_mask_route_grid(small_ds, built_index):
    """explain() renders on every (mask, route) cell without tracing."""
    ds = small_ds
    eng = QueryEngine(built_index)
    masks = (1, 2, 3, 4, 8, 10, 12, ANY_OVERLAP)
    assert len(set(masks)) == 8
    for mask in masks:
        qlo, qhi = make_queries(ds, mask, 0.15, seed=31)
        for route in ("graph", "pruned", "flat"):
            res = eng.search(_req(ds, qlo, qhi, mask, route=route))
            text = res.explain()
            assert f"route: {route}" in text, (iv.mask_name(mask), route)
            assert "trace: (none" in text
    # and one traced cell renders the span tree inline
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    text = eng.search(_req(ds, qlo, qhi, route="graph", trace=True)).explain()
    assert "trace:" in text and "search" in text


# ---- acceptance: sharded deployment ---------------------------------------

def test_sharded_trace_acceptance(small_ds, tmp_path):
    """SearchRequest(trace=True) through engine_auto on a 2-shard host-merge
    deployment -> valid Chrome-trace JSON covering plan / route / per-shard
    search / merge, with explain() printing the same breakdown."""
    from repro.core import IndexSpec
    from repro.distributed import DeploymentSpec, ShardedDeployment
    ds = small_ds
    dep = ShardedDeployment.build(
        ds.vectors, ds.lo, ds.hi, mesh=None,
        spec=DeploymentSpec(n_shards=2,
                            index=IndexSpec(variants=("T", "Tp"), m=8,
                                            ef_con=40)))
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    res = dep.execute(_req(ds, qlo, qhi, trace=True))   # route=None -> auto
    assert res.trace is not None
    names = res.trace.span_names()
    for want in ("sharded_search", "plan", "shard", "fetch", "merge",
                 "route"):
        assert want in names, names
    path = res.trace.save(str(tmp_path / "trace.json"))
    with open(path) as f:
        chrome = json.load(f)
    events = chrome["traceEvents"]
    assert events and all(e["ph"] == "X" for e in events)
    assert {e["name"] for e in events} == set(names)
    text = res.explain()
    assert "shard[0]" in text and "shard[1]" in text
    assert "merge: host" in text and "sharded_search" in text
    # inner shard engines joined the outer trace: exactly one Trace, and each
    # shard's engine dispatch nests under its shard span
    shards = [sp for sp in res.trace.roots[0].children if sp.name == "shard"]
    assert [sp.args["shard"] for sp in shards] == [0, 1]
    assert [c.name for c in shards[0].children][0] == "route"


# ---- serving: one snapshot schema from both servers ------------------------

def test_sync_async_snapshot_schema(small_ds, built_index):
    from repro.serving import (AsyncRetrievalServer, RetrievalServer,
                               SLOPolicy)
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    embed = lambda items: ds.queries[np.asarray(items)]  # noqa: E731

    sync = RetrievalServer(QueryEngine(built_index), embed, k=5, ef=48)
    for i in range(6):
        sync.submit(i, qlo[i], qhi[i], ANY_OVERLAP)
    sync.tick()
    ssnap = sync.snapshot()

    asyn = AsyncRetrievalServer(QueryEngine(built_index), embed, k=5, ef=48,
                                policy=SLOPolicy(max_wait_ms=1.0,
                                                 max_batch=8))
    for i in range(6):
        asyn.submit(i, qlo[i], qhi[i], ANY_OVERLAP)
    asyn.run_until_idle()
    asnap = asyn.snapshot()

    # exp13 reads ONE schema from both servers
    assert set(ssnap) - set(asnap) == set()
    for snap in (ssnap, asnap):
        assert snap["served"] == 6
        assert set(snap["queue_wait_ms"]) == set(snap["e2e_ms"])
        assert snap["e2e_ms"]["p95"] >= snap["queue_wait_ms"]["p50"] >= 0.0


# ---- log + profile ---------------------------------------------------------

def test_progress_rate_limit():
    from repro.obs.log import get_logger
    lg = get_logger("test_obs_progress")
    assert lg.progress("tick", every_s=60.0, done=1) is True
    assert lg.progress("tick", every_s=60.0, done=2) is False   # rate-limited
    assert lg.progress("tick", every_s=60.0, done=3, final=True) is True
    assert lg.progress("other", every_s=60.0) is True           # per-event


def test_peaks_of_an_unknown_chip_raise():
    from repro.obs.profile import V5E, peaks
    assert peaks(V5E)["hbm_bw"] == 819e9
    # an unknown chip is an error, never a v5e default
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v99")


def test_kernel_span_records_bandwidth(small_ds, monkeypatch):
    """A kernel span carries the kernel's modelled bytes, does not wait for
    the kernel, and leaves the answer as the untraced call gives it."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    ds = small_ds
    q = jnp.asarray(ds.queries[:2])
    corpus = jnp.asarray(ds.vectors[:128])
    cand = jnp.asarray(np.broadcast_to(ds.vectors[:8], (2, 8, ds.d)).copy())
    calls = {
        "pairwise_l2_masked": (
            lambda: ops.pairwise_l2_masked(
                q, corpus, jnp.asarray(ds.lo[:128], jnp.float32),
                jnp.asarray(ds.hi[:128], jnp.float32),
                jnp.full(2, -1e9, jnp.float32), jnp.full(2, 1e9, jnp.float32),
                ANY_OVERLAP, bq=8, bn=128),
            ops.pairwise_stream_bytes(2, 128, ds.d, 4)),
        "gathered_l2": (lambda: ops.gathered_l2(q, cand),
                        q.nbytes + cand.nbytes),
    }
    untraced = {name: np.asarray(call()) for name, (call, _) in calls.items()}

    def no_wait(*a, **k):
        raise AssertionError("a traced kernel call waited on the device")

    monkeypatch.setattr(jax, "block_until_ready", no_wait)
    for name, (call, nbytes) in calls.items():
        t = obs.begin_request_trace()
        traced = np.asarray(call())
        trace = obs.end_request_trace(t)
        np.testing.assert_array_equal(traced, untraced[name])
        (sp,) = trace.roots
        assert sp.name == f"kernel:{name}"
        assert sp.args == {"bytes": nbytes}


# ---- profiler bridge -------------------------------------------------------

def _profile_events(log_dir):
    """``(name, start_ns, end_ns, stats)`` of the program's annotations
    (``repro.`` names) in the profile written under ``log_dir``."""
    import glob
    import os
    import warnings
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(obs.PROFILE_PREFIX):
                        out.append((ev.name, ev.start_ns, ev.end_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_span_writes_an_annotation_while_a_profiler_records(tmp_path):
    import jax
    assert not obs.tracing() and obs.span("off") is obs.NULL_SPAN
    with jax.profiler.trace(str(tmp_path)):
        assert obs.tracing() and obs.active_tracer() is None
        with obs.span("outer") as o:
            assert o is not obs.NULL_SPAN
            o.set("n", np.int64(3)).set("f", 0.5).set("variant", "T")
            o.set("shape", (2, 3))
            inner = obs.span("inner")        # explicit start/stop region
            inner.set("k", 7)
            inner.stop()
            inner.stop()                     # a second stop is a no-op
    # the session is over: the fast path is back
    assert not obs.tracing() and obs.span("off") is obs.NULL_SPAN
    ev = {name: (s, e, st) for name, s, e, st in _profile_events(tmp_path)}
    assert set(ev) == {"repro.outer", "repro.inner"}
    assert ev["repro.outer"][2] == {"n": 3, "f": 0.5, "variant": "T",
                                    "shape": "(2, 3)"}
    assert ev["repro.inner"][2] == {"k": 7}
    (os_, oe, _), (is_, ie, _) = ev["repro.outer"], ev["repro.inner"]
    assert os_ <= is_ <= ie <= oe


def test_pruned_execute_under_profiler_writes_spans_and_rows(
        small_ds, built_index, tmp_path):
    """One pruned-route execute under a profiler session: every span of the
    route lands in the profile, and ``repro.pruned`` carries the scan's row
    counts, equal to what the registry counted for the call."""
    import jax
    ds = small_ds
    eng = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    req = _req(ds, qlo, qhi, route="pruned")
    eng.execute(req)                         # compile outside the session
    rows = obs.get_registry().counter("engine_pruned_rows_total",
                                      labels=("kind",))
    kinds = ("needed", "scanned", "to_longest", "bound")
    before = {k: rows.value(kind=k) for k in kinds}
    with jax.profiler.trace(str(tmp_path)):
        res = eng.execute(req)
    events = _profile_events(tmp_path)
    names = {name for name, *_ in events}
    for want in ("search", "plan", "pruned", "slot", "merge", "fetch"):
        assert "repro." + want in names, names
    (pruned,) = [st for name, _, _, st in events if name == "repro.pruned"]
    for k in kinds:
        assert pruned[f"rows_{k}"] == rows.value(kind=k) - before[k]
    assert 0 < pruned["rows_needed"] <= pruned["rows_to_longest"] \
        == pruned["rows_scanned"] <= pruned["rows_bound"]
    assert pruned["slots"] == res.report.slot_count == 2
    slot_stats = [st for name, _, _, st in events if name == "repro.slot"]
    assert len(slot_stats) == 2
    assert all(st["rows"] == 16 and st["block"] == 256 for st in slot_stats)
    assert pruned["rows_bound"] == sum(16 * st["max_blocks"] * 256
                                       for st in slot_stats)


def test_tracer_and_profiler_record_the_same_spans(small_ds, built_index,
                                                   tmp_path):
    import jax
    ds = small_ds
    eng = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=31)
    req = _req(ds, qlo, qhi, route="pruned", trace=True)
    eng.execute(req)
    with jax.profiler.trace(str(tmp_path)):
        res = eng.execute(req)
    tree = res.trace.span_names()
    prof = [name[len(obs.PROFILE_PREFIX):]
            for name, *_ in _profile_events(tmp_path)]
    assert sorted(prof) == sorted(tree)
    assert {"search", "plan", "pruned", "slot", "merge",
            "fetch"} <= set(tree)
    pruned = next(sp for sp, _ in res.trace.walk() if sp.name == "pruned")
    assert {"slots", "rows_needed", "rows_scanned",
            "rows_to_longest", "rows_bound"} <= set(pruned.args)
