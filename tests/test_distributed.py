"""Distributed serving: ShardedDeployment fan-out/merge/fault semantics on
the host path inline; the in-process device-merge tests (bit-parity between
schedules, sharded-vs-single parity grid) skip below 8 devices and run in
CI's ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` lane; an 8-device
subprocess covers the fused kernel when the parent owns only one device."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import (ANY_OVERLAP, LEFT_OVERLAP, QUERY_CONTAINED,
                        QUERY_CONTAINING, RIGHT_OVERLAP, EngineConfig,
                        IndexSpec, QueryEngine, SearchRequest)
from repro.core.hnsw import NO_EDGE
from repro.distributed import (DeploymentSpec, ShardedDeployment,
                               sharded_flat_topk)
from repro.data import make_range_dataset, make_queries, brute_force_topk, recall_at_k

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _mesh8():
    from repro.launch.mesh import make_mesh
    return make_mesh((8,), ("data",))


def test_sharded_flat_single_device(small_ds):
    ds = small_ds
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.2, seed=5)
    # corpus size must divide the shard count (1) — always true
    ids, d = sharded_flat_topk(mesh, jnp.asarray(ds.vectors),
                               jnp.asarray(ds.lo, jnp.float32),
                               jnp.asarray(ds.hi, jnp.float32),
                               jnp.asarray(ds.queries),
                               jnp.asarray(qlo, jnp.float32),
                               jnp.asarray(qhi, jnp.float32),
                               mask=ANY_OVERLAP, k=10)
    tids, tds = brute_force_topk(ds.vectors, ds.lo, ds.hi, ds.queries,
                                 qlo, qhi, ANY_OVERLAP, 10)
    np.testing.assert_allclose(np.sort(np.asarray(d), 1), np.sort(tds, 1),
                               rtol=1e-4, atol=1e-4)


# ---- host path: fan-out/merge/fault semantics, no mesh required ----

def test_deployment_host_merge_matches_single_engine(small_ds, built_index):
    """4 exact shards merged on host == the single-device exact answer."""
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.2, seed=5)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10)
    single = QueryEngine(built_index).search(
        SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10, route="flat"))
    dep = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                 spec=DeploymentSpec(n_shards=4))
    res = dep.execute(req)
    assert res.report.route == "sharded" and res.report.merge == "host"
    assert len(res.report.shards) == 4 and not res.degraded
    np.testing.assert_allclose(np.sort(res.dists, 1), np.sort(single.dists, 1),
                               rtol=1e-4, atol=1e-4)
    assert res.recall_vs(single) == 1.0


def test_shard_loss_degrades_never_raises(small_ds):
    """A failed shard yields a flagged degraded answer with sentinel rows
    from its range — and restore() heals it."""
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.3, seed=6)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10)
    dep = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                 spec=DeploymentSpec(n_shards=4))
    nloc = ds.vectors.shape[0] // 4
    full = dep.execute(req)
    dep.fail(2)
    res = dep.execute(req)
    assert res.degraded and res.report.missing_shards == (2,)
    rep = res.report.shards[2]
    assert rep.shard == 2 and not rep.alive and rep.route == "lost"
    assert rep.k_fetched == 0
    assert all(r.alive for i, r in enumerate(res.report.shards) if i != 2)
    # nothing from the lost shard's row range leaks into the answer
    got = res.ids[res.ids >= 0]
    assert not ((got >= 2 * nloc) & (got < 3 * nloc)).any()
    dep.restore(2)
    healed = dep.execute(req)
    assert not healed.degraded
    np.testing.assert_array_equal(healed.ids, full.ids)


def test_shard_exception_and_heartbeat_timeout_flagged(small_ds):
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.3, seed=7)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=5)
    # a shard raising mid-search is reported as route="error", not re-raised
    dep = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                 spec=DeploymentSpec(n_shards=3))
    dep.shards[1].engine = object()          # .execute() -> AttributeError
    res = dep.execute(req)
    assert res.degraded and res.report.missing_shards == (1,)
    assert res.report.shards[1].route == "error"
    assert not res.report.shards[1].alive
    # idle time never loses a shard, and a shard that raised is sent the
    # next request again: its answer brings it back
    dep2 = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                  spec=DeploymentSpec(n_shards=2))
    full = dep2.execute(req)
    time.sleep(0.02)
    assert not dep2.execute(req).degraded
    dep2.shards[1].engine = object()         # shard 1 starts raising
    res = dep2.execute(req)
    assert res.degraded and res.report.missing_shards == (1,)
    dep2.shards[1].engine = None
    healed = dep2.execute(req)
    assert not healed.degraded
    np.testing.assert_array_equal(healed.ids, full.ids)


def test_per_shard_k_narrowing_and_padding(small_ds):
    """D*k' < k pads the merged answer with sentinel columns instead of
    inventing candidates; k' == k stays exact."""
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.3, seed=8)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10)
    dep = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                 spec=DeploymentSpec(n_shards=4,
                                                     per_shard_k=1))
    res = dep.execute(req)                   # union of 4 candidates, k=10
    assert (res.ids[:, 4:] == NO_EDGE).all()
    assert np.isinf(res.dists[:, 4:]).all()
    assert all(r.k_fetched == 1 for r in res.report.shards)
    assert (res.valid_mask.sum(1) <= 4).all()
    # the merged prefix is sorted and the global best survives narrowing:
    # every shard forwards its local minimum, so the true rank-1 id is there
    exact = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                   spec=DeploymentSpec(n_shards=4))
    eres = exact.execute(req)
    np.testing.assert_array_equal(res.ids[:, 0], eres.ids[:, 0])
    assert (np.diff(res.dists[:, :4], axis=1) >= 0).all()


def test_from_segmented_matches_direct_search(small_ds):
    """Sharding a SegmentedIndex round-robin must not change exact-route
    answers (segments are shared, ids are external either way)."""
    from repro.streaming import SegmentedIndex
    ds = small_ds
    n = 400
    spec = IndexSpec(variants=("T", "Tp"), m=8, ef_con=40)
    seg = SegmentedIndex(spec)
    ids = np.arange(n)
    seg.add(ids[:200], ds.vectors[:200], ds.lo[:200], ds.hi[:200])
    seg.flush()
    seg.add(ids[200:], ds.vectors[200:n], ds.lo[200:n], ds.hi[200:n])
    seg.flush()
    seg.delete(np.arange(20, 40))
    dep = ShardedDeployment.from_segmented(
        seg, spec=DeploymentSpec(n_shards=2))
    assert sum(s.n for s in dep.shards) == len(seg)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.25, seed=9)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=8,
                        route="pruned")
    a = seg.search(req)
    b = dep.execute(req)
    np.testing.assert_allclose(np.sort(a.dists, 1), np.sort(b.dists, 1),
                               rtol=1e-4, atol=1e-4)
    assert b.recall_vs(a) == 1.0


def test_parallel_build_matches_serial(small_ds):
    """build_workers is an execution resource: pooled and serial builds
    produce deployments that answer identically, and both carry a
    build_report (pool size, wall seconds, per-shard seconds, rows/sec).
    On platforms where the spawn pool is unavailable the pooled spec
    degrades to the serial path — the assertions hold either way."""
    ds = small_ds
    ispec = IndexSpec(variants=("T",), m=8, ef_con=32)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.2, seed=7)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10)
    deps = {}
    for w in (0, 2):
        spec = DeploymentSpec(n_shards=4, index=ispec, build_workers=w)
        deps[w] = ShardedDeployment.build(ds.vectors, ds.lo, ds.hi,
                                          spec=spec)
        br = deps[w].build_report
        assert set(br) == {"pool_size", "wall_s", "shard_seconds",
                           "rows_per_sec"}
        assert len(br["shard_seconds"]) == 4
        assert br["rows_per_sec"] > 0
    assert deps[0].build_report["pool_size"] == 0
    a, b = deps[0].execute(req), deps[2].execute(req)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def test_deployment_spec_validation(small_ds):
    ds = small_ds
    with pytest.raises(ValueError):
        DeploymentSpec(n_shards=0)
    with pytest.raises(ValueError):
        DeploymentSpec(build_workers=-1)
    with pytest.raises(ValueError):
        DeploymentSpec(merge="bogus")
    with pytest.raises(ValueError):
        DeploymentSpec(per_shard_k=-1)
    with pytest.raises(TypeError):
        DeploymentSpec(engine={"route": "flat"})
    with pytest.raises(ValueError, match="divisible"):
        ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                               spec=DeploymentSpec(n_shards=7))  # 600 % 7
    dep = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                 spec=DeploymentSpec(n_shards=2))
    with pytest.raises(TypeError, match="SearchRequest"):
        dep.execute(ds.queries)


@pytest.mark.parametrize("merge", ["all_gather", "tournament"])
@pytest.mark.parametrize("layout", ["build", "from_segmented"])
def test_device_merge_is_refused_off_the_flat_layout(small_ds, layout,
                                                     merge):
    """Only the fused flat layout merges on the devices; the others merge
    on the host and refuse a device schedule rather than ignore it."""
    ds = small_ds
    spec = DeploymentSpec(n_shards=2, merge=merge)
    with pytest.raises(ValueError, match="merges on the host"):
        if layout == "build":
            ShardedDeployment.build(ds.vectors, ds.lo, ds.hi, spec=spec)
        else:
            ShardedDeployment.from_segmented(object(), spec=spec)


# ---- device merges: run under the 8-virtual-device CPU lane ----

@needs8
def test_merge_schedules_bit_parity_8dev(small_ds):
    """all_gather and tournament return bit-identical ids AND distances on
    the same 8-shard corpus (distinct distances)."""
    ds = small_ds
    mesh = _mesh8()
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.2, seed=5)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10)
    out = {}
    for merge in ("all_gather", "tournament"):
        dep = ShardedDeployment.flat(
            ds.vectors, ds.lo, ds.hi, mesh=mesh,
            spec=DeploymentSpec(n_shards=8, merge=merge))
        res = dep.execute(req)
        assert res.report.merge == merge
        out[merge] = res
    np.testing.assert_array_equal(out["all_gather"].ids,
                                  out["tournament"].ids)
    np.testing.assert_array_equal(out["all_gather"].dists,
                                  out["tournament"].dists)
    # and both equal the host merge (same candidates, same order)
    host = ShardedDeployment.flat(ds.vectors, ds.lo, ds.hi,
                                  spec=DeploymentSpec(n_shards=8,
                                                      merge="host"))
    np.testing.assert_array_equal(out["all_gather"].ids,
                                  host.execute(req).ids)


@needs8
@pytest.mark.parametrize("mask", [1, 2, 3, 4, 8, 15, 48, 63])
def test_sharded_vs_single_parity_grid_8dev(small_ds, built_index, mask):
    """The smoke grid: every route on every predicate family answers from 8
    shards what one device answers — exactly for the exact routes, at
    matched recall for the graph route (per-shard graphs differ from the
    single graph, so parity there is recall, not bits)."""
    ds = small_ds
    mesh = _mesh8()
    dep = ShardedDeployment.build(
        ds.vectors, ds.lo, ds.hi, mesh=mesh,
        spec=DeploymentSpec(
            n_shards=8,
            index=IndexSpec(variants=("T", "Tp", "Tpp"), m=8, ef_con=40)))
    single = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, mask, 0.25, seed=10 + mask)
    exact = single.search(SearchRequest(ds.queries, (qlo, qhi), mask, k=10,
                                        route="flat"))
    for route in ("flat", "pruned", "graph"):
        res = dep.execute(SearchRequest(ds.queries, (qlo, qhi), mask, k=10,
                                        ef=64, route=route))
        assert res.report.merge == "host" and not res.degraded
        if route == "graph":
            assert res.recall_vs(exact) >= 0.9, (mask, route)
        else:
            np.testing.assert_allclose(np.sort(res.dists, 1),
                                       np.sort(exact.dists, 1),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{mask}/{route}")
            assert res.recall_vs(exact) == 1.0, (mask, route)


@needs8
def test_fused_flat_device_path_matches_host_8dev(small_ds):
    """The fused shard_map path (per_shard_k narrowing included) returns
    what the host-orchestrated merge returns, and a dead shard is masked
    identically on device."""
    ds = small_ds
    mesh = _mesh8()
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.25, seed=12)
    req = SearchRequest(ds.queries, (qlo, qhi), ANY_OVERLAP, k=10)
    for fk in (0, 4):
        dev = ShardedDeployment.flat(
            ds.vectors, ds.lo, ds.hi, mesh=mesh,
            spec=DeploymentSpec(n_shards=8, per_shard_k=fk))
        host = ShardedDeployment.flat(
            ds.vectors, ds.lo, ds.hi,
            spec=DeploymentSpec(n_shards=8, per_shard_k=fk, merge="host"))
        dev.fail(5)
        host.fail(5)
        a = dev.execute(req)
        b = host.execute(req)
        assert a.degraded and a.report.missing_shards == (5,)
        assert a.report.shards[5].route == "lost"
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_allclose(a.dists, b.dists, rtol=1e-5, atol=1e-6)


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import ANY_OVERLAP, QUERY_CONTAINED
    from repro.distributed import sharded_flat_topk
    from repro.data import make_range_dataset, make_queries, brute_force_topk

    ds = make_range_dataset(n=512, d=16, n_queries=8, quantize=32, seed=1)
    for mask in (ANY_OVERLAP, QUERY_CONTAINED):
        qlo, qhi = make_queries(ds, mask, 0.25, seed=2)
        tids, tds = brute_force_topk(ds.vectors, ds.lo, ds.hi, ds.queries,
                                     qlo, qhi, mask, 10)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        for merge in ("all_gather", "tournament"):
            ids, d = sharded_flat_topk(
                mesh, jnp.asarray(ds.vectors), jnp.asarray(ds.lo, jnp.float32),
                jnp.asarray(ds.hi, jnp.float32), jnp.asarray(ds.queries),
                jnp.asarray(qlo, jnp.float32), jnp.asarray(qhi, jnp.float32),
                mask=mask, k=10, merge=merge)
            np.testing.assert_allclose(np.sort(np.asarray(d), 1), np.sort(tds, 1),
                                       rtol=1e-4, atol=1e-4)
            # ids must be correctly rebased to global
            got = set(int(x) for x in np.asarray(ids)[0] if x >= 0)
            want = set(int(x) for x in tids[0] if x >= 0)
            dmat = np.sort(np.asarray(d)[0])
            tmat = np.sort(tds[0])
            ok = np.allclose(dmat, tmat, rtol=1e-4, atol=1e-4)
            assert ok, (merge, mask)
    print("OK-8DEV")
""")


@pytest.mark.slow
def test_sharded_flat_8dev_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.join(os.path.dirname(__file__), ".."), env=env)
    assert "OK-8DEV" in r.stdout, r.stdout + r.stderr


# ---- build layout: one plan, every shard dispatched before any is awaited --

# the five predicates of the benchmark's wide mix, and one disjunction
WIDE_MASKS = (LEFT_OVERLAP, QUERY_CONTAINED, RIGHT_OVERLAP, QUERY_CONTAINING,
              ANY_OVERLAP, LEFT_OVERLAP | RIGHT_OVERLAP)


@pytest.fixture(scope="module")
def odd_ds():
    """603 rows: four shards of unequal size."""
    return make_range_dataset(n=603, d=16, n_queries=64, quantize=32, seed=3)


@pytest.fixture(scope="module")
def pruned_dep(odd_ds):
    ds = odd_ds
    return ShardedDeployment.build(
        ds.vectors, ds.lo, ds.hi,
        spec=DeploymentSpec(n_shards=4, engine=EngineConfig(route="pruned"),
                            index=IndexSpec(variants=("T", "Tp", "Tpp"),
                                            builder="scan")))


def assert_exact(ids, dists, ds, queries, qlo, qhi, mask, k):
    """Served answers equal brute force + eval_predicate: distances to f32
    rounding, ids wherever the distance at that rank is not tied."""
    ref_ids, ref_d = brute_force_topk(ds.vectors, ds.lo, ds.hi, queries,
                                      qlo, qhi, mask, k)
    ids, dists = np.asarray(ids), np.asarray(dists)
    np.testing.assert_allclose(dists, ref_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(ids < 0, np.isinf(ref_d))
    close = np.isclose(ref_d[:, 1:], ref_d[:, :-1], rtol=1e-5, atol=1e-5)
    tie = np.isinf(ref_d)
    tie[:, 1:] |= close
    tie[:, :-1] |= close
    np.testing.assert_array_equal(ids[~tie], ref_ids[~tie])


@pytest.mark.parametrize("Q", [1, 13, 64])
@pytest.mark.parametrize("mask", WIDE_MASKS)
def test_build_deployment_matches_brute_force(odd_ds, pruned_dep, mask, Q):
    ds = odd_ds
    qlo, qhi = make_queries(ds, mask, 0.1, seed=40 + mask)
    req = SearchRequest(ds.queries[:Q], (qlo[:Q], qhi[:Q]), mask, k=10)
    res = pruned_dep.execute(req)
    assert not res.degraded and res.report.merge == "host"
    assert [s.route for s in res.report.shards] == ["pruned"] * 4
    assert_exact(res.ids, res.dists, ds, ds.queries[:Q], qlo[:Q], qhi[:Q],
                 mask, 10)


def test_build_shards_share_one_domain(odd_ds, pruned_dep):
    doms = [s.engine.index.domain for s in pruned_dep.shards]
    assert all(d is doms[0] for d in doms)
    np.testing.assert_array_equal(
        doms[0].values, np.unique(np.concatenate([odd_ds.lo, odd_ds.hi])))
    assert [s.n for s in pruned_dep.shards] == [150, 151, 151, 151]


def test_deployment_plans_once_per_request(odd_ds, pruned_dep, monkeypatch):
    """The deployment plans each request once, through its own ``plan``,
    and hands the plan to every shard; no shard engine plans on its own."""
    from repro.core.mstg import MSTGIndex
    ds = odd_ds
    calls = []
    plan_batch = MSTGIndex.plan_batch
    monkeypatch.setattr(MSTGIndex, "plan_batch",
                        lambda *a: calls.append("plan") or plan_batch(*a))
    plan = pruned_dep.plan
    monkeypatch.setattr(pruned_dep, "plan",
                        lambda *a: calls.append("dep") or plan(*a))
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.1, seed=5)
    for _ in range(3):
        res = pruned_dep.execute(SearchRequest(ds.queries[:13],
                                               (qlo[:13], qhi[:13]),
                                               ANY_OVERLAP, k=10))
        assert not res.degraded
    assert calls == ["dep", "plan"] * 3
    assert res.report.slot_count == 4 * len(plan(ANY_OVERLAP, qlo[:13],
                                                 qhi[:13]))


def test_shard_spans_end_before_the_fetch(odd_ds, pruned_dep):
    """Every shard is dispatched before any shard's answers are awaited:
    each ``shard`` span ends before the ``fetch`` span begins; each shard
    call is counted on /metrics."""
    from repro import obs
    ds = odd_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.1, seed=6)
    req = SearchRequest(ds.queries[:13], (qlo[:13], qhi[:13]), ANY_OVERLAP,
                        k=10)
    counter = obs.get_registry().counter("deployment_shard_calls_total",
                                         labels=("shard",))
    before = [counter.value(shard=str(i)) for i in range(4)]
    with obs.capture() as tr:
        pruned_dep.execute(req)
    spans = [sp for sp, _ in tr.trace().walk()]
    shard = [sp for sp in spans if sp.name == "shard"]
    (fetch,) = [sp for sp in spans if sp.name == "fetch"
                and sp in tr.trace().roots[0].children]
    assert [sp.args["shard"] for sp in shard] == [0, 1, 2, 3]
    assert all(sp.args["rows"] == 13 and sp.args["slots"] > 0
               for sp in shard)
    assert max(sp.t_stop for sp in shard) <= fetch.t_start
    (merge,) = [sp for sp in spans if sp.name == "merge"
                and sp in tr.trace().roots[0].children]
    assert merge.args["rows"] == 4 * 13 * 10
    assert [counter.value(shard=str(i)) - before[i]
            for i in range(4)] == [1, 1, 1, 1]


@pytest.mark.parametrize("route", ["pruned", "flat", "graph"])
def test_dispatch_then_collect_is_execute(small_ds, built_index, route):
    """``execute`` is ``dispatch`` then ``collect``: the same answers, bit
    for bit, and the same report."""
    ds = small_ds
    eng = QueryEngine(built_index)
    for mask in (QUERY_CONTAINED, ANY_OVERLAP):
        qlo, qhi = make_queries(ds, mask, 0.2, seed=mask)
        req = SearchRequest(ds.queries, (qlo, qhi), mask, k=10, route=route)
        a = eng.execute(req)
        b = eng.collect(eng.dispatch(req))
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)
        assert repr(a.report) == repr(b.report)


def test_fail_degrades_a_build_deployment(odd_ds, pruned_dep):
    """fail() on a pruned build deployment: degraded answers with
    missing_shards set, exact over the shards left; restore() heals."""
    ds = odd_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.3, seed=7)
    req = SearchRequest(ds.queries[:13], (qlo[:13], qhi[:13]), ANY_OVERLAP,
                        k=10)
    pruned_dep.fail(1)
    try:
        res = pruned_dep.execute(req)
    finally:
        pruned_dep.restore(1)
    assert res.degraded and res.report.missing_shards == (1,)
    assert res.report.shards[1].route == "lost"
    keep = np.ones(len(ds.lo), bool)
    keep[150:301] = False                    # shard 1's rows
    lo = np.where(keep, ds.lo, 2e9)          # rows no predicate can keep
    ref_ids, ref_d = brute_force_topk(ds.vectors, lo, np.where(keep, ds.hi,
                                                               2e9),
                                      ds.queries[:13], qlo[:13], qhi[:13],
                                      ANY_OVERLAP, 10)
    np.testing.assert_allclose(res.dists, ref_d, rtol=1e-5, atol=1e-5)
    assert not pruned_dep.execute(req).degraded


_FOUR_DEVICE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path[:0] = ["src", "tests"]
    import numpy as np
    import jax
    from repro.core import EngineConfig, IndexSpec, SearchRequest
    from repro.data import make_range_dataset, make_queries
    from repro.distributed import DeploymentSpec, ShardedDeployment
    from test_distributed import WIDE_MASKS, assert_exact

    ds = make_range_dataset(n=603, d=16, n_queries=64, quantize=32, seed=3)
    dep = ShardedDeployment.build(
        ds.vectors, ds.lo, ds.hi,
        spec=DeploymentSpec(n_shards=4, engine=EngineConfig(route="pruned"),
                            index=IndexSpec(variants=("T", "Tp", "Tpp"),
                                            builder="scan")))
    for mask in WIDE_MASKS:
        qlo, qhi = make_queries(ds, mask, 0.1, seed=40 + mask)
        for Q in (1, 13, 64):
            res = dep.execute(SearchRequest(ds.queries[:Q],
                                            (qlo[:Q], qhi[:Q]), mask, k=10))
            assert not res.degraded
            assert_exact(res.ids, res.dists, ds, ds.queries[:Q], qlo[:Q],
                         qhi[:Q], mask, 10)
    homes = [s.engine.corpus.devices() for s in dep.shards]
    assert homes == [{d} for d in jax.devices()], homes
    print("OK-4DEV")
""")


def test_build_deployment_on_four_devices_subprocess():
    """The same answers with each shard on its own (virtual) device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_PROG],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       env=env)
    assert "OK-4DEV" in r.stdout, r.stdout + r.stderr[-3000:]
