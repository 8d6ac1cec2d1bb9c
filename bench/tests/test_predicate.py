"""The benchmark's own predicate and its exact grid counts."""
import numpy as np
import pytest

from bench.lib import data, predicate, traffic


@pytest.fixture(scope="module")
def corpus():
    lo, hi, lo_pos, hi_pos, grid = data.make_intervals(
        5, 3000, dist="uniform", span=1000.0, max_width_frac=0.25, grid=32)
    return lo, hi, lo_pos, hi_pos, grid


def test_atoms_cover_any_overlap():
    rng = np.random.default_rng(0)
    lo, hi = np.sort(rng.integers(0, 10, (2, 4000)), axis=0)
    ql, qh = np.sort(rng.integers(0, 10, (2, 4000)), axis=0)
    atoms = [predicate.holds(m, lo, hi, ql, qh) for m in (1, 2, 4, 8)]
    union = np.logical_or.reduce(atoms)
    assert np.array_equal(union, predicate.holds(15, lo, hi, ql, qh))
    assert np.array_equal(union, (lo <= qh) & (ql <= hi))


@pytest.mark.parametrize("mask", sorted(predicate.MASKS.values()))
def test_grid_counts_match_direct_count(corpus, mask):
    lo, hi, lo_pos, hi_pos, grid = corpus
    K = grid.shape[0]
    counts = predicate.grid_counts(lo_pos, hi_pos, K, mask)
    for i in range(0, K, 3):
        for j in range(i, K, 5):
            direct = np.count_nonzero(
                predicate.holds(mask, lo, hi, grid[i], grid[j]))
            assert counts[i, j] == direct


def test_requests_hit_their_selectivity(corpus):
    lo, hi, lo_pos, hi_pos, grid = corpus
    c = data.Corpus(np.zeros((lo.shape[0], 2), np.float32), lo, hi, lo_pos,
                    hi_pos, grid, np.zeros((8, 2), np.float32))
    mix = {"loop": "open", "rate_qps": 50, "k": 10, "selectivity": 0.05,
           "selectivity_tol": 0.25,
           "masks": ["left_overlap", "query_contained", "right_overlap",
                     "query_containing"]}
    r = traffic.make_requests(mix, c, 2**33 + 5, 4.0)
    assert r.mask.shape == (200,)
    assert np.all(np.diff(r.due) >= 0) and r.due.max() <= 4.0
    for m, ql, qh, cnt in zip(r.mask, r.qlo, r.qhi, r.count):
        direct = np.count_nonzero(predicate.holds(m, lo, hi, ql, qh))
        assert direct == cnt
        assert abs(cnt - 0.05 * lo.shape[0]) <= 0.25 * 0.05 * lo.shape[0]
    again = traffic.make_requests(mix, c, 2**33 + 5, 4.0)
    assert np.array_equal(again.qlo, r.qlo) and np.array_equal(again.due,
                                                               r.due)
