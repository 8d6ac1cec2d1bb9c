"""The plain reference, its lower-precision control, and the comparison that
decides ``correct``.

Reference: exact filtered k-NN by brute force. Every corpus row is tested
against the request's predicate and scored with the squared L2 distance
``|q|^2 - 2 q.x + |x|^2`` in float32 at full precision on the device,
block by block; the best ``k + SLACK`` rows of each request are then scored
again in float64 on the host, and the best ``k`` of those are the answer.

Control: the same brute force with ``q.x`` contracted the way a TPU's
``Precision.HIGH`` does it, as three bfloat16 passes (each operand split
into a bfloat16 head and a bfloat16 tail, the tail-by-tail product left
out), and its own distances kept: the reference computed in the precision
just below the float32-at-highest that the configuration states. It is
written out, not asked of the compiler, so it reads the same on any
platform.

Comparison, over a sample of the answered requests (all its numbers are
in ``check_numbers``): ``bad`` counts answers that break the filter or the
shape of an answer, ``unanswered`` counts requests that never got an
answer, ``dist_gap`` is the widest relative gap between a served distance
and the reference's distance at the same rank, and ``dist_err`` is the
widest relative gap between a served distance and the float64 distance of
the row the server named. Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Dict

import numpy as np

from . import predicate

SLACK = 32
ROW_BLOCK = 1 << 16
QUERY_BLOCK = 128
NO_ID = -1


def _dot_bf16x3(q, x):
    """q @ x.T as TPU's Precision.HIGH computes it: each float32 operand
    split into a bfloat16 head and a bfloat16 tail, three products (the
    tail-by-tail one left out), float32 accumulation. The splits use
    ``reduce_precision``, which the compiler may not fold away as it may a
    round trip through ``astype``; the products of bfloat16 values are
    exact in a full-precision float32 contraction."""
    import jax
    import jax.numpy as jnp

    def split(a):
        head = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        tail = jax.lax.reduce_precision(a - head, exponent_bits=8,
                                        mantissa_bits=7)
        return head, tail

    qh, ql = split(q)
    xh, xl = split(x)

    def dot(a, b):
        return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST)

    return dot(qh, xh) + (dot(qh, xl) + dot(ql, xh))


def row_blocks(n: int) -> int:
    """Fewest blocks of at most ROW_BLOCK rows that divide n evenly."""
    b = -(-n // ROW_BLOCK)
    while n % b:
        b += 1
    return b


@functools.lru_cache(maxsize=None)
def _screen_program(n: int, d: int, width: int, control: bool):
    """Jitted brute force over the whole corpus for one block of queries:
    (Qb, width) best candidates by float32 distance, masked by predicate."""
    import jax
    import jax.numpy as jnp

    n_blocks = row_blocks(n)
    rb = n // n_blocks

    def run(x, lo, hi, q, ql, qh, mask):
        qn = jnp.sum(q * q, axis=1)

        def body(carry, b):
            best_d, best_i = carry
            xb = jax.lax.dynamic_slice_in_dim(x, b * rb, rb)
            lb = jax.lax.dynamic_slice_in_dim(lo, b * rb, rb)
            hb = jax.lax.dynamic_slice_in_dim(hi, b * rb, rb)
            if control:
                dot = _dot_bf16x3(q, xb)
            else:
                dot = jnp.dot(q, xb.T, precision=jax.lax.Precision.HIGHEST)
            dist = qn[:, None] - 2.0 * dot + jnp.sum(xb * xb, axis=1)[None]
            ok = predicate.holds(mask[:, None], lb[None], hb[None],
                                 ql[:, None], qh[:, None], xp=jnp)
            dist = jnp.where(ok, dist, jnp.inf)
            neg, pos = jax.lax.top_k(-dist, width)
            cat_d = jnp.concatenate([best_d, -neg], axis=1)
            cat_i = jnp.concatenate([best_i, b * rb + pos], axis=1)
            neg, pos = jax.lax.top_k(-cat_d, width)
            return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

        init = (jnp.full((q.shape[0], width), jnp.inf, jnp.float32),
                jnp.full((q.shape[0], width), NO_ID, jnp.int32))
        (best_d, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
        return jnp.where(jnp.isfinite(best_d), best_i, NO_ID), best_d

    return jax.jit(run)


def _screen(corpus_dev, lo_dev, hi_dev, n, d, queries, qlo, qhi, mask,
            width, control):
    import jax.numpy as jnp
    prog = _screen_program(n, d, width, control)
    ids, dists = [], []
    Q = queries.shape[0]
    for a in range(0, Q, QUERY_BLOCK):
        sl = slice(a, min(a + QUERY_BLOCK, Q))
        m = sl.stop - sl.start
        padq = QUERY_BLOCK - m

        def padded(v, fill):
            return np.concatenate([v, np.full((padq,) + v.shape[1:], fill,
                                              v.dtype)])
        i, dd = prog(corpus_dev, lo_dev, hi_dev,
                     jnp.asarray(padded(queries[sl], 0.0)),
                     jnp.asarray(padded(qlo[sl], 1.0), jnp.float32),
                     jnp.asarray(padded(qhi[sl], 0.0), jnp.float32),
                     jnp.asarray(padded(mask[sl], 0), jnp.int32))
        ids.append(np.asarray(i)[:m])
        dists.append(np.asarray(dd)[:m])
    return np.concatenate(ids), np.concatenate(dists)


def exact_dist(vectors: np.ndarray, q: np.ndarray, ids: np.ndarray
               ) -> np.ndarray:
    """float64 squared distances of rows ``ids`` (inf where id < 0)."""
    safe = np.where(ids >= 0, ids, 0)
    diff = vectors[safe].astype(np.float64) - q.astype(np.float64)[..., None, :]
    out = np.einsum("...kd,...kd->...k", diff, diff)
    return np.where(ids >= 0, out, np.inf)


def answers(corpus, queries, qlo, qhi, mask, k: int, control: bool = False):
    """(ids (Q, k), dists (Q, k)) of the reference, or of the control.

    The reference's distances are float64; the control's are its own
    float32 ones. Missing ranks (fewer than k rows qualify) hold NO_ID and
    inf."""
    import jax.numpy as jnp
    n, d = corpus.vectors.shape
    corpus_dev = jnp.asarray(corpus.vectors)
    lo_dev = jnp.asarray(corpus.lo, jnp.float32)
    hi_dev = jnp.asarray(corpus.hi, jnp.float32)
    width = min(k if control else k + SLACK, n // row_blocks(n))
    ids, dists = _screen(corpus_dev, lo_dev, hi_dev, n, d, queries, qlo, qhi,
                         mask, width, control)
    del corpus_dev, lo_dev, hi_dev
    if control:
        return ids.astype(np.int64), dists.astype(np.float64)
    exact = exact_dist(corpus.vectors, queries, ids)
    order = np.argsort(exact, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(ids, order, 1).astype(np.int64),
            np.take_along_axis(exact, order, 1))


def check_numbers(corpus, queries, qlo, qhi, mask, served_ids, served_dists,
                  ref_ids, ref_dists) -> Dict[str, float]:
    """The numbers compared for a sample of answered requests.

    ``bad``: answers that name a row out of range or twice, a row that
    fails the request's predicate, a missing row where the reference has
    one, or a row where the reference has none. ``dist_gap``: max over
    ranks of |served distance - reference distance| / reference distance.
    ``dist_err``: max over ranks of |served distance - float64 distance of
    the named row| / that distance."""
    n = corpus.vectors.shape[0]
    sid = np.asarray(served_ids, np.int64)
    sd = np.asarray(served_dists, np.float64)
    has_ref = np.isfinite(ref_dists)
    has_srv = sid >= 0
    in_range = has_srv & (sid < n)
    safe = np.where(in_range, sid, 0)
    keeps = predicate.holds(mask[:, None], corpus.lo[safe], corpus.hi[safe],
                            qlo[:, None], qhi[:, None])
    srt = np.sort(np.where(has_srv, sid, -1 - np.arange(sid.shape[1])), 1)
    dup = np.zeros_like(has_srv)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    bad = (np.sum(has_srv & ~(in_range & keeps)) + np.sum(dup)
           + np.sum(has_ref != has_srv))
    both = has_ref & in_range
    ref = np.where(both, ref_dists, 1.0)
    gap = np.where(both, np.abs(sd - ref) / np.maximum(ref, 1e-30), 0.0)
    named = exact_dist(corpus.vectors, queries, np.where(both, sid, -1))
    named = np.where(both, named, 1.0)
    err = np.where(both, np.abs(sd - named) / np.maximum(named, 1e-30), 0.0)
    return {"bad": float(bad),
            "dist_gap": float(gap.max(initial=0.0)),
            "dist_err": float(err.max(initial=0.0))}


def sample(rng: np.random.Generator, answered: np.ndarray,
           count: np.ndarray, mask: np.ndarray, size: int) -> np.ndarray:
    """Indices of requests to check: ``size`` drawn from the answered ones,
    plus for each mask the answered request whose predicate keeps most
    rows (the longest scan)."""
    answered = np.asarray(answered)
    if answered.size == 0:
        return answered
    pick = set(rng.choice(answered, min(size, answered.size),
                          replace=False).tolist())
    for m in np.unique(mask[answered]):
        rows = answered[mask[answered] == m]
        pick.add(int(rows[np.argmax(count[rows])]))
    return np.array(sorted(pick), np.int64)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[name] <= limit for name, limit in limits.items())


def check_lines(numbers: Dict[str, float], limits: Dict[str, float]
                ) -> Dict[str, Dict[str, float]]:
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in limits}


def control_or_reference(corpus, req_idx: np.ndarray, requests, k: int,
                         control: bool):
    q = corpus.queries[requests.vector[req_idx]]
    return answers(corpus, q, requests.qlo[req_idx], requests.qhi[req_idx],
                   requests.mask[req_idx], k, control=control)


def compare(corpus, requests, req_idx, served_ids, served_dists,
            unanswered: int, ref_ids, ref_dists) -> Dict[str, float]:
    """The compared numbers for the sampled requests ``req_idx``, given
    what was served for them and the reference's answers."""
    q = corpus.queries[requests.vector[req_idx]]
    nums = check_numbers(corpus, q, requests.qlo[req_idx],
                         requests.qhi[req_idx], requests.mask[req_idx],
                         served_ids, served_dists, ref_ids, ref_dists)
    nums["unanswered"] = float(unanswered)
    return nums
