"""Distributed filtered top-k over a corpus-sharded MSTG deployment.

Architecture (DESIGN.md §5): the corpus (vectors + ranges [+ per-shard MSTG
arrays]) is sharded along ``corpus_axis``; each device computes a local
filtered top-k, then shards exchange results. Two merge schedules:

* ``all_gather`` — every shard gathers all (Q, k) lists, one collective,
  bytes/device ∝ D·Q·k. Simple, latency-optimal for small D.
* ``tournament`` — log2(D) ``ppermute`` rounds, each merging two k-lists;
  bytes/device ∝ log2(D)·Q·k. The beyond-paper schedule for pod-scale D
  (D=512: 9 rounds vs 512x gather) — see EXPERIMENTS.md §Perf.

Both schedules accept local lists narrower than the global ``k`` (the
deployment's ``per_shard_k`` fan-in knob): every intermediate merge retains
``min(k, candidates so far)`` entries, so no candidate that can reach the
global top-k is ever dropped and the two schedules stay bit-identical for
distinct distances. When ``D * k' < k`` the result is padded with
``NO_EDGE``/``inf`` columns. Dead shards (``alive`` mask) contribute only
sentinel rows — a lost device degrades recall, never correctness of the
merge itself.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.flat import flat_search
from repro.core.hnsw import NO_EDGE


def _pad_to_k(ids, dists, k: int):
    """Right-pad (Q, w) lists to (Q, k) with NO_EDGE/inf sentinel columns."""
    w = ids.shape[1]
    if w >= k:
        return ids, dists
    pad = [(0, 0), (0, k - w)]
    return (jnp.pad(ids, pad, constant_values=NO_EDGE),
            jnp.pad(dists, pad, constant_values=jnp.inf))


def global_topk_merge(ids, dists, k: int, axis: str):
    """all_gather merge inside shard_map: (Q, k') local -> (Q, k) global.

    Accepts local width k' != k (the ``per_shard_k`` fan-in knob); pads with
    sentinels when the union D*k' holds fewer than k candidates."""
    all_ids = jax.lax.all_gather(ids, axis)     # (D, Q, k')
    all_d = jax.lax.all_gather(dists, axis)
    D = all_ids.shape[0]
    Q = all_ids.shape[1]
    w = all_ids.shape[2]
    flat_ids = jnp.moveaxis(all_ids, 0, 1).reshape(Q, D * w)
    flat_d = jnp.moveaxis(all_d, 0, 1).reshape(Q, D * w)
    kk = min(k, D * w)
    neg, pos = jax.lax.top_k(-flat_d, kk)
    return _pad_to_k(jnp.take_along_axis(flat_ids, pos, 1), -neg, k)


def tournament_topk_merge(ids, dists, k: int, axis: str):
    """Recursive-halving merge: log2(D) ppermute rounds of k-list merges.

    After round r, device i holds the merged top-k of its 2^(r+1)-device
    group; all devices finish with the global top-k (butterfly exchange).
    Each round keeps ``min(k, 2w)`` of the 2w concatenated candidates, so a
    narrow local width k' < k widens toward k instead of truncating — the
    final list is bit-identical to :func:`global_topk_merge` whenever
    distances are distinct."""
    D = int(jax.lax.axis_size(axis))
    rounds = int(np.log2(D))
    assert (1 << rounds) == D, "tournament merge needs power-of-two shards"
    for r in range(rounds):
        stride = 1 << r
        perm = [(int(i), int((i + stride) if (i // stride) % 2 == 0 else (i - stride)))
                for i in range(D)]
        other_ids = jax.lax.ppermute(ids, axis, perm)
        other_d = jax.lax.ppermute(dists, axis, perm)
        cat_ids = jnp.concatenate([ids, other_ids], axis=1)
        cat_d = jnp.concatenate([dists, other_d], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, min(k, cat_d.shape[1]))
        ids = jnp.take_along_axis(cat_ids, pos, 1)
        dists = -neg
    return _pad_to_k(ids, dists, k)


MERGE_SCHEDULES = {"all_gather": global_topk_merge,
                   "tournament": tournament_topk_merge}


def resolve_merge(merge: str, n_shards: int) -> str:
    """``auto`` -> all_gather for small meshes, tournament for pow2 D > 8."""
    if merge == "auto":
        if n_shards > 8 and (n_shards & (n_shards - 1)) == 0:
            return "tournament"
        return "all_gather"
    if merge not in MERGE_SCHEDULES:
        raise ValueError(f"unknown merge schedule {merge!r}; "
                         f"expected one of {sorted(MERGE_SCHEDULES)} or 'auto'")
    return merge


def sharded_flat_topk(mesh: Mesh, corpus, lo, hi, queries, ql, qh, *, mask: int,
                      k: int, corpus_axis: str = "data",
                      merge: str = "all_gather", per_shard_k: int = 0,
                      alive=None,
                      use_kernel: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact distributed RRANN: corpus sharded on ``corpus_axis``, queries
    replicated, result replicated. Local ids are rebased to global ids.

    ``per_shard_k`` < k narrows the per-shard fan-in (less merge traffic,
    possibly lower recall); 0 means fetch the full k per shard. ``alive`` is
    an optional (D,) bool mask — a False shard contributes only sentinels,
    yielding the degraded-recall answer a lost device would."""
    D = mesh.shape[corpus_axis]
    n = corpus.shape[0]
    assert n % D == 0, f"corpus size {n} not divisible by {D} shards"
    nloc = n // D
    k_loc = min(per_shard_k, k) if per_shard_k else k
    k_loc = min(k_loc, nloc)
    merge_fn = MERGE_SCHEDULES[resolve_merge(merge, D)]
    alive_arr = (jnp.ones((D,), bool) if alive is None
                 else jnp.asarray(alive, bool))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(corpus_axis, None), P(corpus_axis), P(corpus_axis),
                  P(None, None), P(None), P(None), P(None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)
    def run(c, l, h, q, a, b, ok):
        ids, d = flat_search(c, l, h, q, a, b, mask=mask, k=k_loc,
                             use_kernel=use_kernel)
        shard = jax.lax.axis_index(corpus_axis)
        gids = jnp.where(ids != NO_EDGE, ids + shard * nloc, NO_EDGE)
        up = ok[shard]
        gids = jnp.where(up, gids, NO_EDGE)
        d = jnp.where(up, d, jnp.inf)
        return merge_fn(gids, d, k, corpus_axis)

    return run(corpus, lo, hi, queries, ql, qh, alive_arr)
