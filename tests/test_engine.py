"""Vectorized planner (property-based vs the scalar Theorem 4.1 reference)
and the QueryEngine facade (routing, padding, end-to-end recall)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as hst

from repro.core import (ANY_OVERLAP, QUERY_CONTAINED, QUERY_CONTAINING,
                        EngineConfig, MSTGIndex, Overlaps, QueryEngine,
                        SearchRequest, intervals as iv)
from repro.core import segment_tree as st
from repro.core.engine import (ROUTE_GRAPH, ROUTE_PRUNED, _next_pow2,
                               _scan_rows)
from repro.core.flat import _node_of_position, _pruned_search_variant
from repro.core.hnsw import NO_EDGE
from repro.data import brute_force_topk, make_queries, make_range_dataset


def _req(queries, qlo, qhi, mask, route=None, **kw):
    return SearchRequest(queries, (qlo, qhi), mask, route=route, **kw)


# ---- plan_batch_ranked vs scalar plan_searches_ranked ----

@settings(max_examples=150, deadline=None)
@given(hst.integers(1, 63), hst.integers(2, 40), hst.data())
def test_plan_batch_ranked_matches_scalar(mask, K, data):
    """Slot-for-slot agreement on random rank bounds, including the Allen
    BEFORE/AFTER bits and exact-vs-between endpoint encodings."""
    rng = np.random.default_rng(data.draw(hst.integers(0, 2**31)))
    Q = 32
    fl = rng.integers(-1, K, Q)
    exact_l = rng.integers(0, 2, Q).astype(bool) & (fl >= 0)
    cl = np.where(exact_l, fl, fl + 1)
    fr = np.maximum(fl, rng.integers(-1, K, Q))
    exact_r = rng.integers(0, 2, Q).astype(bool) & (fr >= cl)
    cr = np.where(exact_r, fr, fr + 1)

    slots = iv.plan_batch_ranked(mask, fl, cl, fr, cr, K)
    for qi in range(Q):
        ref = iv.plan_searches_ranked(mask, int(fl[qi]), int(cl[qi]),
                                      int(fr[qi]), int(cr[qi]), K)
        assert len(slots) == len(ref)
        for s, t in zip(slots, ref):
            assert s.variant == t.variant
            got = (int(s.version[qi]), int(s.key_lo[qi]), int(s.key_hi[qi]))
            assert got == (t.version, t.key_lo, t.key_hi), (
                iv.mask_name(mask), qi, got, t)


def test_plan_batch_ranked_empty_mask_and_shapes():
    slots = iv.plan_batch_ranked(0, np.zeros(4, np.int64), np.zeros(4, np.int64),
                                 np.ones(4, np.int64), np.ones(4, np.int64), 8)
    assert slots == []
    slots = iv.plan_batch_ranked(ANY_OVERLAP, np.zeros(5, np.int64),
                                 np.zeros(5, np.int64), np.full(5, 3),
                                 np.full(5, 3), 8)
    assert [s.variant for s in slots] == [iv.VARIANT_T, iv.VARIANT_TP]
    for s in slots:
        assert s.version.shape == s.key_lo.shape == s.key_hi.shape == (5,)


def test_plan_batch_rejects_inverted_ranges(built_index):
    with pytest.raises(ValueError):
        built_index.plan_batch(ANY_OVERLAP, np.array([5.0]), np.array([1.0]))


def test_plan_batch_rejects_missing_variant(small_ds):
    ds = small_ds
    idx = MSTGIndex(ds.vectors, ds.lo, ds.hi, variants=("T",), m=8, ef_con=40)
    with pytest.raises(ValueError, match="needs variants"):
        idx.plan_batch(QUERY_CONTAINING, np.array([1.0]), np.array([2.0]))


# ---- QueryEngine ----

def test_engine_graph_matches_flat_ground_truth(small_ds, built_index):
    """End-to-end: graph path vs flat route ground truth at high recall."""
    ds = small_ds
    eng = QueryEngine(built_index)
    for mask in (ANY_OVERLAP, QUERY_CONTAINED, QUERY_CONTAINING):
        qlo, qhi = make_queries(ds, mask, 0.15, seed=31)
        truth = eng.search(_req(ds.queries, qlo, qhi, mask, route="flat"))
        graph = eng.search(_req(ds.queries, qlo, qhi, mask, route="graph",
                                ef=96))
        assert graph.recall_vs(truth) >= 0.9, iv.mask_name(mask)


def test_engine_routes_agree_and_pruned_is_exact(small_ds, built_index):
    ds = small_ds
    eng = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.1, seed=37)
    tids, tds = brute_force_topk(ds.vectors, ds.lo, ds.hi, ds.queries,
                                 qlo, qhi, ANY_OVERLAP, 10)
    pruned = eng.search(_req(ds.queries, qlo, qhi, Overlaps(), route="pruned"))
    np.testing.assert_allclose(np.sort(pruned.dists, 1), np.sort(tds, 1),
                               rtol=1e-4, atol=1e-4)
    flat = eng.search(_req(ds.queries, qlo, qhi, Overlaps(), route="flat"))
    np.testing.assert_allclose(np.sort(flat.dists, 1), np.sort(tds, 1),
                               rtol=1e-4, atol=1e-4)
    assert pruned.report.route == "pruned" and flat.report.route == "flat"


def test_engine_auto_routing_by_selectivity(small_ds, built_index):
    ds = small_ds
    eng = QueryEngine(built_index, config=EngineConfig(flat_threshold=0.15))
    # narrow query -> low selectivity -> pruned; wide -> graph
    qlo_n, qhi_n = make_queries(ds, ANY_OVERLAP, 0.02, seed=41)
    qlo_w, qhi_w = make_queries(ds, ANY_OVERLAP, 0.6, seed=41)
    est_n = eng.estimate_selectivity(ANY_OVERLAP, qlo_n, qhi_n)
    est_w = eng.estimate_selectivity(ANY_OVERLAP, qlo_w, qhi_w)
    assert est_n.mean() < est_w.mean()
    assert eng.route_for(ANY_OVERLAP, qlo_n, qhi_n) == ROUTE_PRUNED
    assert eng.route_for(ANY_OVERLAP, qlo_w, qhi_w) == ROUTE_GRAPH
    # selectivity estimate is exact here (sample == corpus)
    want = np.stack([np.asarray(iv.eval_predicate(
        ANY_OVERLAP, ds.lo, ds.hi, qlo_n[i], qhi_n[i])).mean()
        for i in range(len(qlo_n))])
    np.testing.assert_allclose(est_n, want, atol=1e-12)


def test_engine_padding_is_invisible(small_ds, built_index):
    """Bucketed (padded) batches return exactly what unpadded batches do."""
    ds = small_ds
    eng_pad = QueryEngine(built_index, config=EngineConfig(pad_queries=True))
    eng_raw = QueryEngine(built_index, config=EngineConfig(pad_queries=False))
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.15, seed=43)
    for Q in (1, 3, 7):  # all pad up to buckets
        req = _req(ds.queries[:Q], qlo[:Q], qhi[:Q], Overlaps(),
                   route=ROUTE_GRAPH)
        a = eng_pad.search(req)
        b = eng_raw.search(req)
        assert a.ids.shape == (Q, 10)
        np.testing.assert_allclose(np.sort(a.dists, 1), np.sort(b.dists, 1),
                                   rtol=1e-4, atol=1e-4)


def test_engine_pruned_exact_despite_bad_estimator(small_ds, built_index):
    """The pruned candidate cap comes from the plan (exact bound), not the
    sampled selectivity estimate — a pathological estimator must not cause
    truncation (regression: cap used to be 2x the sampled selectivity)."""
    ds = small_ds
    eng = QueryEngine(built_index, config=EngineConfig(selectivity_sample=4))
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.05, seed=47)
    tids, tds = brute_force_topk(ds.vectors, ds.lo, ds.hi, ds.queries,
                                 qlo, qhi, ANY_OVERLAP, 10)
    pids, pds = eng.search_pruned(ds.queries, qlo, qhi, ANY_OVERLAP, k=10)
    np.testing.assert_allclose(np.sort(pds, 1), np.sort(tds, 1),
                               rtol=1e-4, atol=1e-4)


def _needed_rows(index, slots) -> int:
    """The candidate rows a plan's slot scans need, recounted on the host:
    for each query of each slot, the members of every node of its key
    range's decomposition inserted at or before its version."""
    total = 0
    for s in slots:
        fv = index.variants[s.variant]
        for ver, lo, hi in zip(s.version, s.key_lo, s.key_hi):
            for lvl, idx in st.decompose(int(lo), int(hi), fv.Kpad):
                a, b = fv.node_off[lvl, idx], fv.node_off[lvl, idx + 1]
                total += int(np.sum(fv.member_ver[lvl, a:b] <= ver))
    return total


@pytest.mark.parametrize("Q", [12, 8], ids=["padded", "unpadded"])
@pytest.mark.parametrize("mask", [1, 2, 3, 4, 8, 10, 12, ANY_OVERLAP])
def test_pruned_scan_row_counters(small_ds, built_index, mask, Q):
    """Each slot scan needs no more rows than the batch's longest prefix
    holds, which is what the scan loop ran, no more than its cap (the bound
    that keeps the pruned route exact); the needed rows match a host
    recount; the registry rises by the same sums."""
    from repro import obs
    ds = small_ds
    eng = QueryEngine(built_index)
    qlo, qhi = make_queries(ds, mask, 0.15, seed=31)
    queries, qlo, qhi = ds.queries[:Q], qlo[:Q], qhi[:Q]
    slots = eng.plan(mask, qlo, qhi)
    *_, scans = eng._run_pruned(queries, qlo, qhi, mask, 10, slots=slots)
    rows = _scan_rows(scans)
    Qp = _next_pow2(Q)
    for (needed, to_longest, scanned, bound), (total, _, max_blocks,
                                               block) in zip(rows, scans):
        assert total.shape == (Qp,)
        assert 0 <= needed <= to_longest == scanned <= bound
        assert bound == Qp * max_blocks * block
    assert rows[:, 0].sum() == _needed_rows(built_index, slots)

    counter = obs.get_registry().counter("engine_pruned_rows_total",
                                         labels=("kind",))
    kinds = ("needed", "to_longest", "scanned", "bound")
    before = [counter.value(kind=k) for k in kinds]
    eng.execute(_req(queries, qlo, qhi, mask, route=ROUTE_PRUNED, k=10))
    rose = [counter.value(kind=k) - b for k, b in zip(kinds, before)]
    assert rose == list(rows.sum(axis=0))


def _assert_exact(ds, queries, qlo, qhi, mask, ids, d, k=10):
    """Served answers against brute force: the same distances rank by rank,
    and every id named qualifies and sits at the distance served."""
    _, tds = brute_force_topk(ds.vectors, ds.lo, ds.hi, queries, qlo, qhi,
                              mask, k)
    np.testing.assert_allclose(d, tds, rtol=1e-5, atol=1e-5)
    for qi in range(queries.shape[0]):
        got = ids[qi][ids[qi] >= 0]
        assert got.size == np.isfinite(tds[qi]).sum()
        assert np.asarray(iv.eval_predicate(mask, ds.lo[got], ds.hi[got],
                                            qlo[qi], qhi[qi])).all()
        diff = ds.vectors[got] - queries[qi]
        np.testing.assert_allclose(np.einsum("nd,nd->n", diff, diff),
                                   d[qi, :got.size], rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def wide_ds():
    """Unquantized endpoints: ~2,400 distinct values, so Kpad = 4096 and a
    decomposition has P = 26 nodes (16 on the 32-value grid of small_ds)."""
    return make_range_dataset(n=1500, d=16, n_queries=12, seed=7)


@pytest.fixture(scope="module")
def wide_index(wide_ds):
    ds = wide_ds
    return MSTGIndex(ds.vectors, ds.lo, ds.hi, variants=("T", "Tp", "Tpp"),
                     builder="scan")


@pytest.mark.parametrize("grid,mask,Q,tier", [
    *[pytest.param("narrow", m, Q, "float32", id=f"{m}-{Q}-float32")
      for m in (1, 4, 12, ANY_OVERLAP) for Q in (12, 8)],
    pytest.param("narrow", ANY_OVERLAP, 12, "int8", id=f"{ANY_OVERLAP}-12-int8"),
    *[pytest.param("wide", m, Q, tier, id=f"wide-{m}-{Q}-{tier}")
      for m, Q, tier in ((1, 12, "float32"), (4, 8, "float32"),
                         (12, 12, "float32"), (ANY_OVERLAP, 12, "float32"),
                         (ANY_OVERLAP, 8, "int8"))]])
def test_pruned_scan_stops_at_longest_prefix(request, grid, mask, Q, tier):
    """The slot scan's loop stops at the batch's longest candidate prefix
    whatever its cap and block: answers at the default cap, at the widest
    cap and at many small blocks agree bit for bit, and with brute force.
    On the wide endpoint grid (P = 26 nodes a query) the ids are brute
    force's, rank for rank."""
    ds = request.getfixturevalue("small_ds" if grid == "narrow" else "wide_ds")
    index = request.getfixturevalue("built_index" if grid == "narrow"
                                    else "wide_index")
    eng = QueryEngine(index, config=EngineConfig(storage_dtype=tier))
    qlo, qhi = make_queries(ds, mask, 0.15, seed=31)
    queries, qlo, qhi = ds.queries[:Q], qlo[:Q], qhi[:Q]
    runs = ({}, {"max_candidates": ds.vectors.shape[0]}, {"block": 8})
    (ids, d), *others = [eng.search_pruned(queries, qlo, qhi, mask, k=10,
                                           **kw) for kw in runs]
    for ids_o, d_o in others:
        np.testing.assert_array_equal(ids_o, ids)
        np.testing.assert_array_equal(d_o, d)
    _assert_exact(ds, queries, qlo, qhi, mask, ids, d)
    if grid == "wide":
        assert st.max_cover_nodes(index.variants["T"].Kpad) >= 26
        tids, _ = brute_force_topk(ds.vectors, ds.lo, ds.hi, queries, qlo,
                                   qhi, mask, 10)
        np.testing.assert_array_equal(ids, tids)
    for kw in runs:
        *_, scans = eng._run_pruned(queries, qlo, qhi, mask, 10, **kw)
        for total, n_run, max_blocks, block in scans:
            longest = int(np.asarray(total).max())
            assert int(n_run) == -(-longest // block) <= max_blocks


def test_pruned_scan_of_one_row_runs_one_block(built_index):
    """A slot whose every task is empty but one, whose prefix is one row:
    the loop runs one block, and the answer is that row."""
    eng = QueryEngine(built_index)
    fv = built_index.variants["T"]

    def first_member(key):
        """The node of a one-key range, and its first member's slice index
        if that member alone is in the prefix at its own version."""
        (lvl, node), = st.decompose(key, key, fv.Kpad)
        a, b = fv.node_off[lvl, node], fv.node_off[lvl, node + 1]
        alone = b == a + 1 or (b > a + 1 and fv.member_ver[lvl, a]
                               < fv.member_ver[lvl, a + 1])
        return (lvl, a) if alone else None

    key, (lvl, a) = next((j, m) for j in range(fv.Kpad)
                         if (m := first_member(j)) is not None)
    row = int(fv.members[lvl, a])
    Q = 8
    version = np.full(Q, -1, np.int64)
    key_lo, key_hi = np.ones(Q, np.int64), np.zeros(Q, np.int64)
    version[0] = fv.member_ver[lvl, a]
    key_lo[0] = key_hi[0] = key
    slot = iv.PlanSlot("T", version, key_lo, key_hi)
    qlo = np.full(Q, built_index.lo[row])
    qhi = np.full(Q, built_index.hi[row])
    queries = np.zeros((Q, built_index.vectors.shape[1]), np.float32)
    ids, d, scans = eng._run_pruned(queries, qlo, qhi, ANY_OVERLAP, 10,
                                    slots=[slot])
    (total, n_run, _, _), = scans
    np.testing.assert_array_equal(np.asarray(total), [1] + [0] * (Q - 1))
    assert int(n_run) == 1
    ids = np.asarray(ids)
    assert ids[0, 0] == row and (ids[0, 1:] < 0).all() and (ids[1:] < 0).all()
    assert np.isfinite(np.asarray(d)[0, 0])


def test_pruned_scan_longest_prefix_in_whole_blocks(small_ds, built_index):
    """A batch whose longest prefix is an exact multiple of the block: the
    loop runs exactly that many blocks, drops none, and answers as brute
    force does."""
    ds = small_ds
    eng = QueryEngine(built_index)
    mask = 1                                    # a single-slot plan
    qlo, qhi = make_queries(ds, mask, 0.15, seed=31)
    queries, qlo, qhi = ds.queries[:8], qlo[:8], qhi[:8]
    *_, scans = eng._run_pruned(queries, qlo, qhi, mask, 10)
    (total, *_), = scans
    longest = int(np.asarray(total).max())
    block = next(b for b in range(longest // 2, 0, -1) if longest % b == 0)
    ids, d, scans = eng._run_pruned(queries, qlo, qhi, mask, 10, block=block)
    (_, n_run, _, _), = scans
    assert longest // block >= 2 and int(n_run) == longest // block
    rows = _scan_rows(scans)[0]
    assert rows[1] == rows[2] == 8 * longest
    _assert_exact(ds, queries, qlo, qhi, mask, np.asarray(ids), np.asarray(d))


def test_pruned_scan_of_empty_tasks_runs_no_block(built_index):
    """All prefixes empty: the loop runs no block and every answer is
    ``NO_EDGE`` at ``inf``."""
    eng = QueryEngine(built_index)
    Q, k = 8, 10
    queries = jnp.zeros((Q, built_index.vectors.shape[1]), jnp.float32)
    ql = jnp.zeros(Q, jnp.float32)
    ids, d, total, n_run = _pruned_search_variant(
        eng.pruned_dev("T"), eng.lo, eng.hi, queries, ql, ql + 1e9,
        jnp.full(Q, -1, jnp.int32), jnp.ones(Q, jnp.int32),
        jnp.zeros(Q, jnp.int32), pred_mask_bits=ANY_OVERLAP, k=k,
        Kpad=built_index.variants["T"].Kpad, block=256, max_blocks=3)
    assert int(n_run) == 0 and not np.asarray(total).any()
    np.testing.assert_array_equal(np.asarray(ids), np.full((Q, k), NO_EDGE))
    assert np.isinf(np.asarray(d)).all()


def _node_of_position_by_gather(pos, starts, cum, levels, off):
    """The slot scan's mapping before the select: the node's slot by a
    compare against ``cum``, then its start, level and slice start by
    ``take_along_axis``."""
    P = cum.shape[1]
    slot = np.clip(np.sum(pos[None, :, None] >= cum[:, None, :], axis=2),
                   0, P - 1)
    inner = pos[None, :] - np.take_along_axis(starts, slot, 1)
    return (np.take_along_axis(levels, slot, 1),
            np.take_along_axis(off, slot, 1) + inner)


@pytest.mark.parametrize("P", [16, 34])
def test_node_of_position_matches_gather(P):
    """The select over the P nodes reads the level and member index the
    gathers read, at every position of every query's prefix: nodes empty
    at this version, nodes past the decomposition (invalid, with junk
    levels and offsets), a padded query with no node at all; positions at
    or past a query's total read ``(0, pos)``."""
    rng = np.random.default_rng(P)
    Q, Lv, W = 9, P // 2, 5000
    valid = rng.random((Q, P)) < 0.7
    levels = np.where(valid, rng.integers(0, Lv, (Q, P)),
                      rng.integers(-5, 50, (Q, P))).astype(np.int32)
    off = np.where(valid, rng.integers(0, W - 64, (Q, P)),
                   rng.integers(-W, 2 * W, (Q, P))).astype(np.int32)
    plen = rng.integers(0, 64, (Q, P)) * (rng.random((Q, P)) < 0.6)
    plen = np.where(valid, plen, 0).astype(np.int32)
    plen[-1] = 0                                 # a padded query
    cum = np.cumsum(plen, axis=1, dtype=np.int32)
    starts = cum - plen
    total = cum[:, -1]
    assert total[-1] == 0 and total.max() > 256
    pos = np.arange(int(total.max()) + 300, dtype=np.int32)
    lvl, midx = (np.asarray(a) for a in _node_of_position(
        jnp.asarray(pos), jnp.asarray(starts), jnp.asarray(cum),
        jnp.asarray(levels), jnp.asarray(off - starts)))
    want_lvl, want_midx = _node_of_position_by_gather(pos, starts, cum,
                                                      levels, off)
    inside = pos[None, :] < total[:, None]
    np.testing.assert_array_equal(lvl[inside], want_lvl[inside])
    np.testing.assert_array_equal(midx[inside], want_midx[inside])
    assert (lvl[~inside] == 0).all()
    np.testing.assert_array_equal(midx[~inside],
                                  np.broadcast_to(pos, inside.shape)[~inside])


def _gathers_in_while_bodies(jaxpr, in_body=False):
    """Operand shapes of every gather inside a ``while`` body, walking
    nested jaxprs (jit calls, conds, scans) too."""
    found = []
    for eqn in jaxpr.eqns:
        if in_body and eqn.primitive.name == "gather":
            found.append(tuple(eqn.invars[0].aval.shape))
        for name, v in eqn.params.items():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _gathers_in_while_bodies(
                        sub, in_body or (eqn.primitive.name == "while"
                                         and name == "body_jaxpr"))
    return found


def _scan_jaxpr(tier, n, Qp=16, d=32, Kpad=128, block=256):
    """The slot scan traced at these shapes, with no array made."""
    Lv = st.num_levels(Kpad)
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    arrays = dict(members=sds((Lv, n), i32), member_ver=sds((Lv, n), i32),
                  node_off=sds((Lv, Kpad + 1), i32))
    if tier == "int8":
        arrays.update(codes=sds((n, d), jnp.int8), code_scale=sds((d,), f32),
                      code_offset=sds((d,), f32), code_sq_norm=sds((n,), f32))
    else:
        arrays["vectors"] = sds((n, d), f32)
    fn = functools.partial(_pruned_search_variant, pred_mask_bits=ANY_OVERLAP,
                           k=10, Kpad=Kpad, block=block, max_blocks=4)
    return jax.make_jaxpr(fn)(
        arrays, sds((n,), f32), sds((n,), f32), sds((Qp, d), f32),
        sds((Qp,), f32), sds((Qp,), f32), sds((Qp,), i32), sds((Qp,), i32),
        sds((Qp,), i32))


@pytest.mark.parametrize("tier", ["float32", "int8"])
def test_scan_loop_reads_no_node_table_by_gather(tier):
    """The slot scan's loop body maps positions to nodes with no gather
    from a (Qp, P) table: its gathers read the members, the endpoints, the
    corpus (or its codes) and the running top-k only."""
    Qp, n, Kpad = 16, 4096, 128
    shapes = _gathers_in_while_bodies(_scan_jaxpr(tier, n).jaxpr)
    assert (st.num_levels(Kpad) * n,) in shapes  # the member ids, laid flat
    assert (Qp, st.max_cover_nodes(Kpad)) not in shapes, shapes


def test_scan_refuses_member_table_past_int32_index():
    """Member ids are read by a flat int32 index: a member table of 2**31
    ids or more is refused when the scan is traced."""
    with pytest.raises(ValueError, match="int32"):
        _scan_jaxpr("float32", 2**31 // st.num_levels(128))


def test_engine_empty_batch_and_empty_predicate(built_index, small_ds):
    eng = QueryEngine(built_index)
    res = eng.search(_req(np.zeros((0, small_ds.d), np.float32),
                          np.zeros(0), np.zeros(0), ANY_OVERLAP, k=5))
    assert res.ids.shape == (0, 5) and res.dists.shape == (0, 5)
    assert len(res) == 0 and list(res) == []
    qlo = np.full(3, -50.0)
    qhi = np.full(3, -40.0)
    res = eng.search(_req(small_ds.queries[:3], qlo, qhi, QUERY_CONTAINED,
                          k=5))
    assert (res.ids < 0).all() and np.isinf(res.dists).all()
    assert not res.valid_mask.any()


def test_next_pow2():
    assert [_next_pow2(x) for x in (1, 2, 3, 7, 8, 9)] == [1, 2, 4, 8, 8, 16]


def test_selectivity_cache_bounded_fifo_eviction(small_ds, built_index):
    """Overflow evicts the oldest entries only (FIFO), never the whole memo,
    and the hit/miss/eviction counters stay consistent throughout."""
    ds = small_ds
    eng = QueryEngine(built_index, config=EngineConfig(sel_cache_max=8))
    vals = built_index.domain.values
    qlo = vals[:12].copy()                    # 12 distinct rank signatures
    qhi = qlo + (vals[-1] - vals[0])
    _, h1, m1 = eng._estimate_cached(15, qlo, qhi)
    assert (h1, m1) == (0, 12)
    assert len(eng._sel_cache) == 8           # bounded, not cleared
    assert eng.sel_cache_evictions == 4       # the 4 oldest fell out
    # newest 8 still hit; oldest 4 miss again and evict the next-oldest 4
    _, h2, m2 = eng._estimate_cached(15, qlo[4:], qhi[4:])
    assert (h2, m2) == (8, 0)
    _, h3, m3 = eng._estimate_cached(15, qlo[:4], qhi[:4])
    assert (h3, m3) == (0, 4)
    assert len(eng._sel_cache) == 8
    assert eng.sel_cache_evictions == 8
    assert eng.sel_cache_hits == h1 + h2 + h3
    assert eng.sel_cache_misses == m1 + m2 + m3
    # estimates themselves are unaffected by eviction
    est, _, _ = eng._estimate_cached(15, qlo, qhi)
    want = eng.estimate_selectivity(15, qlo, qhi)
    np.testing.assert_array_equal(est, want)


def test_auto_route_parity_with_pinned_route(small_ds, built_index):
    """The auto-route regression fix: an auto-routed request must execute the
    *same* plan as pinning the route it selects — identical ids, distances,
    slot count, and variants — with selectivity answered from the O(1) rank
    table before any device work (no sample scan on the request path)."""
    ds = small_ds
    eng = QueryEngine(built_index, config=EngineConfig(flat_threshold=0.15))
    for sel, want_route in ((0.02, ROUTE_PRUNED), (0.6, ROUTE_GRAPH)):
        qlo, qhi = make_queries(ds, ANY_OVERLAP, sel, seed=53)
        auto = eng.search(_req(ds.queries, qlo, qhi, ANY_OVERLAP))
        assert auto.report.route == want_route
        assert auto.report.requested == "auto"
        pinned = eng.search(_req(ds.queries, qlo, qhi, ANY_OVERLAP,
                                 route=want_route))
        np.testing.assert_array_equal(auto.ids, pinned.ids)
        np.testing.assert_array_equal(auto.dists, pinned.dists)
        assert auto.report.slot_count == pinned.report.slot_count
        assert auto.report.variants == pinned.report.variants
        # route_for agrees with what execute() actually did
        assert eng.route_for(ANY_OVERLAP, qlo, qhi) == want_route


def test_auto_route_work_model_default(small_ds, built_index):
    """Default routing is the work model: at this corpus size the exact
    pruned scan's estimated work (sel * n) stays under the beam's (ef * S)
    for any selectivity, and route_for/execute agree."""
    ds = small_ds
    eng = QueryEngine(built_index)          # flat_threshold=None -> work model
    n = built_index.vectors.shape[0]
    for sel in (0.05, 0.6):
        qlo, qhi = make_queries(ds, ANY_OVERLAP, sel, seed=61)
        est = eng.estimate_selectivity(ANY_OVERLAP, qlo, qhi)
        scan_work = est.mean() * n
        beam_work = 64 * eng._max_slots
        want = ROUTE_PRUNED if scan_work <= beam_work else ROUTE_GRAPH
        assert eng.route_for(ANY_OVERLAP, qlo, qhi, ef=64) == want
        res = eng.search(_req(ds.queries, qlo, qhi, ANY_OVERLAP))
        assert res.report.route == want
        pinned = eng.search(_req(ds.queries, qlo, qhi, ANY_OVERLAP,
                                 route=want))
        np.testing.assert_array_equal(res.ids, pinned.ids)
        np.testing.assert_array_equal(res.dists, pinned.dists)


def test_selectivity_table_built_and_bounded(small_ds, built_index):
    """Small domains get the O(1) table; its estimates equal the sample scan
    (here sample == corpus, so both are exact)."""
    eng = QueryEngine(built_index)
    assert eng._sel_index is not None
    assert eng._sel_index.K == built_index.domain.K
    assert eng._sel_index.m == built_index.vectors.shape[0]
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.3, seed=59)
    est = eng.estimate_selectivity(ANY_OVERLAP, qlo, qhi)
    want = np.stack([np.asarray(iv.eval_predicate(
        ANY_OVERLAP, ds.lo, ds.hi, qlo[i], qhi[i])).mean()
        for i in range(len(qlo))])
    np.testing.assert_allclose(est, want, atol=1e-12)


def test_legacy_constructor_knobs_warn_once_and_fold(built_index):
    """Bare constructor knobs still work but warn exactly once per process
    (attributed to the caller) and fold into the typed EngineConfig; unknown
    knobs and non-EngineConfig configs are rejected outright."""
    import warnings as w
    from repro.core.engine import reset_deprecation_warnings
    reset_deprecation_warnings()
    with w.catch_warnings(record=True) as rec:
        w.simplefilter("always")
        eng1 = QueryEngine(built_index, pad_queries=False, sel_cache_max=7)
        eng2 = QueryEngine(built_index, selectivity_sample=3)
    deps = [r for r in rec if issubclass(r.category, DeprecationWarning)]
    assert len(deps) == 1                     # once per process, not per call
    assert deps[0].filename == __file__       # stacklevel points at the caller
    assert eng1.config.pad_queries is False and eng1.config.sel_cache_max == 7
    assert eng2.config.selectivity_sample == 3
    # knobs layered on an explicit config win over that config
    base = EngineConfig(sel_cache_max=5, pad_queries=False)
    with w.catch_warnings():
        w.simplefilter("ignore", DeprecationWarning)
        eng3 = QueryEngine(built_index, config=base, sel_cache_max=9)
    assert eng3.config.sel_cache_max == 9 and eng3.config.pad_queries is False
    with pytest.raises(TypeError, match="unknown QueryEngine knob"):
        QueryEngine(built_index, beam_width=32)
    with pytest.raises(TypeError, match="EngineConfig"):
        QueryEngine(built_index, config={"route": "flat"})
    reset_deprecation_warnings()


def test_engine_config_validates_and_replaces():
    cfg = EngineConfig()
    assert cfg.route == "auto" and cfg.flat_threshold is None
    assert cfg.replace(route="pruned").route == "pruned"
    assert cfg.route == "auto"                # replace() copies, frozen intact
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.route = "flat"
    for bad in (dict(route="beam"), dict(graph_fanout=0),
                dict(graph_chunk=-1), dict(graph_chunk="wide"),
                dict(selectivity_sample=0), dict(sel_cache_max=0)):
        with pytest.raises(ValueError):
            EngineConfig(**bad)
        with pytest.raises(ValueError):
            cfg.replace(**bad)                # replace() re-validates


def test_request_wins_over_config_wins_over_heuristic(small_ds, built_index):
    """The documented precedence: a SearchRequest field beats the
    EngineConfig value, which beats the backend heuristic."""
    ds = small_ds
    qlo, qhi = make_queries(ds, ANY_OVERLAP, 0.2, seed=67)
    eng = QueryEngine(built_index, config=EngineConfig(route="pruned"))
    res = eng.search(_req(ds.queries, qlo, qhi, ANY_OVERLAP))
    assert res.report.route == "pruned"       # config overrides auto-routing
    res = eng.search(_req(ds.queries, qlo, qhi, ANY_OVERLAP, route="flat"))
    assert res.report.route == "flat"         # request overrides config
    # fanout: request > config > backend heuristic (CPU heuristic is 1)
    eng2 = QueryEngine(built_index, config=EngineConfig(graph_fanout=2))
    assert eng2._resolve_fanout(64, None) == 2
    assert eng2._resolve_fanout(64, 5) == 5
    assert QueryEngine(built_index)._resolve_fanout(64, None) == 1
