"""Exact predicate-filtered search engines (TPU-native MSTG execution).

Two engines (DESIGN.md §2 "flat path"):

* ``flat_search`` — fused predicate + brute-force distances over the whole
  corpus (the MXU-roofline path; also the test/benchmark ground truth).
* ``flat_search_pruned`` — uses the MSTG segment-tree decomposition to touch
  only qualifying *member slices*: every decomposition node stores its members
  grouped contiguously in insertion (=version) order, so the valid candidates
  of a node at version x are a PREFIX of its slice. Work scales with
  selectivity instead of n — the paper's pruning argument, executed as blocked
  gathers + matmuls instead of graph traversal. Exact (recall 1.0) by
  construction.

Both return squared-L2 top-k.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import intervals as iv
from . import segment_tree as st
from .hnsw import NO_EDGE

INF = jnp.inf
# Exact routes contract at full float32: a TPU's default f32 matmul is one
# bf16 pass, which reorders near neighbours against the float32 reference.
HIGHEST = jax.lax.Precision.HIGHEST


def _pairwise_l2(queries: jnp.ndarray, corpus: jnp.ndarray) -> jnp.ndarray:
    """(Q, d) x (N, d) -> (Q, N) squared L2 via the MXU-friendly expansion."""
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)
    cn = jnp.sum(corpus * corpus, axis=1)
    return qn - 2.0 * jnp.dot(queries, corpus.T, precision=HIGHEST) + cn[None, :]


@functools.partial(jax.jit, static_argnames=("mask", "k", "use_kernel"))
def flat_search(corpus: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                queries: jnp.ndarray, ql: jnp.ndarray, qh: jnp.ndarray,
                *, mask: int, k: int, use_kernel: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact filtered k-NN: (Q, k) ids + squared distances (+inf / NO_EDGE pad
    when fewer than k objects qualify)."""
    if use_kernel:
        from repro.kernels import ops as kops
        d = kops.pairwise_l2_masked(queries, corpus, lo, hi, ql, qh, mask)
    else:
        sel = iv.eval_predicate(mask, lo[None, :], hi[None, :],
                                ql[:, None], qh[:, None])       # (Q, N)
        d = jnp.where(sel, _pairwise_l2(queries, corpus), INF)
    neg, idx = jax.lax.top_k(-d, k)
    ids = jnp.where(jnp.isfinite(neg), idx, NO_EDGE).astype(jnp.int32)
    return ids, -neg


@functools.partial(jax.jit, static_argnames=("mask", "k", "block"))
def flat_search_blocked(corpus, lo, hi, queries, ql, qh, *, mask: int, k: int,
                        block: int = 4096):
    """Exact filtered k-NN with a scanned running top-k: the (Q, N) distance
    matrix never materializes in HBM — per block it lives in VMEM and only the
    (Q, k) running winners persist. This is what makes the distributed serve
    step compute-bound (EXPERIMENTS.md §Perf iteration 6)."""
    N, d = corpus.shape
    Q = queries.shape[0]
    block = min(block, N)
    Np = -(-N // block) * block
    pad = Np - N
    if pad:
        corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
        lo = jnp.pad(lo, (0, pad), constant_values=jnp.nan)  # NaN fails all
        hi = jnp.pad(hi, (0, pad), constant_values=jnp.nan)  # RR comparisons
    qn = jnp.sum(queries * queries, axis=1, keepdims=True)

    def body(carry, i):
        top_d, top_i = carry
        c = jax.lax.dynamic_slice_in_dim(corpus, i * block, block, 0)
        l = jax.lax.dynamic_slice_in_dim(lo, i * block, block, 0)
        h = jax.lax.dynamic_slice_in_dim(hi, i * block, block, 0)
        cn = jnp.sum(c * c, axis=1)
        dist = qn - 2.0 * jnp.dot(queries, c.T, precision=HIGHEST) + cn[None, :]
        sel = iv.eval_predicate(mask, l[None, :], h[None, :],
                                ql[:, None], qh[:, None])
        dist = jnp.where(sel, dist, INF)
        ids = i * block + jnp.arange(block)
        cat_d = jnp.concatenate([top_d, dist], axis=1)
        cat_i = jnp.concatenate([top_i, jnp.broadcast_to(ids[None], (Q, block))
                                 .astype(jnp.int32)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, 1)), None

    top0 = (jnp.full((Q, k), INF, jnp.float32),
            jnp.full((Q, k), NO_EDGE, jnp.int32))
    (top_d, top_i), _ = jax.lax.scan(body, top0, jnp.arange(Np // block))
    top_i = jnp.where(jnp.isfinite(top_d), top_i, NO_EDGE)
    return top_i, top_d


def _node_of_position(pos, starts, cum, levels, shift):
    """Map candidate positions to the decomposition node that holds them.

    ``pos`` is (B,) candidate positions; ``starts``, ``cum``, ``levels`` and
    ``shift`` are (Q, P) per node: where its prefix starts and ends in
    candidate space, its tree level, and its slice start in ``members``
    less ``starts``. Position ``pos`` lies in the one node with ``starts <=
    pos < cum`` (a node with an empty prefix holds none), so each value is
    read by a select and a sum over the P nodes, with no gather. Returns
    (Q, B) ``(level, member index)``; a position at or past the query's
    total lies in no node and reads ``(0, pos)``."""
    p = pos[None, None, :]
    hit = (p >= starts[:, :, None]) & (p < cum[:, :, None])       # (Q, P, B)
    lvl = jnp.sum(jnp.where(hit, levels[:, :, None], 0), axis=1)
    midx = pos[None, :] + jnp.sum(jnp.where(hit, shift[:, :, None], 0), axis=1)
    return lvl, midx


@functools.partial(jax.jit, static_argnames=("pred_mask_bits", "k", "Kpad",
                                              "block", "max_blocks"))
def _pruned_search_variant(arrays: dict, lo_attr, hi_attr, queries, ql, qh,
                           version, key_lo, key_hi, *, pred_mask_bits: int,
                           k: int, Kpad: int, block: int, max_blocks: int):
    """One variant's pruned scan: decomposition -> member prefixes -> blocked
    fused distance + running top-k. ``pred_mask_bits`` re-checks the exact
    predicate on gathered candidates (cheap; guards rank-boundary ties and
    lets one variant serve any sub-mask of its plan).

    A query's candidates are its P nodes' member prefixes laid end to end;
    each block of the loop takes ``block`` positions of that space. A
    position is mapped to its node by :func:`_node_of_position`, a compare
    against the nodes' bounds and a masked sum over P, which reads the
    node's level and member index with no gather from the (Q, P) tables.
    The loop body's gathers are the member ids (from ``members`` laid flat),
    their endpoints for the re-check, the candidate rows and the running
    top-k.

    Returns ``(ids, dists, total, n_run)``: ``total`` is each query's (Q,)
    int32 candidate-prefix length, the rows its answer needs; ``n_run`` is
    the int32 count of blocks the loop ran. The loop stops on the device at
    the batch's longest prefix, ``n_run = min(max_blocks, ceil(max(total) /
    block))``: past it every query's rows are out of its prefix, so a block
    there could only add ``INF`` / ``NO_EDGE`` entries. ``max_blocks`` is
    the static cap; the answer is exact only while ``total <= max_blocks *
    block``."""
    # quantized layouts carry "codes" (+ affine params) instead of a float32
    # "vectors" table; dict keys are static under jit, so this picks the
    # gather source at trace time with no runtime branch
    quantized = "codes" in arrays
    vectors = None if quantized else arrays["vectors"]
    if quantized:
        # fold the affine dequant into the query side once (same identity as
        # the compressed flat scan): dist = cq - 2 (q*scale).code + sq_norm.
        # The gathered code tile is then consumed with a single cast +
        # contraction — no per-element scale/offset pass, no diff tensor.
        wq = queries * arrays["code_scale"][None, :]                  # (Q, d)
        cq = (jnp.sum(queries * queries, axis=1)
              - 2.0 * (queries @ arrays["code_offset"]))             # (Q,)
    members, member_ver = arrays["members"], arrays["member_ver"]
    node_off = arrays["node_off"]
    Q, d = queries.shape
    levels, idxs, valid = jax.vmap(lambda a, b: st.decompose_jax(a, b, Kpad))(key_lo, key_hi)
    P = levels.shape[1]

    off = node_off[levels, idxs]                                  # (Q, P) slice starts
    cnt = node_off[levels, idxs + 1] - off                        # (Q, P) member counts
    cnt = jnp.where(valid, cnt, 0)

    # valid prefix length per node at this version: member versions ascend
    # within a slice -> binary search, vectorized over (Q, P).
    def prefix_len(lvl, o, c, ver):
        def bs(state, _):
            lo_i, hi_i = state
            mid = (lo_i + hi_i) // 2
            v = member_ver[lvl, jnp.clip(o + mid, 0, members.shape[1] - 1)]
            go_right = (mid < c) & (v <= ver)
            return (jnp.where(go_right, mid + 1, lo_i),
                    jnp.where(go_right, hi_i, mid)), None
        iters = int(np.ceil(np.log2(max(int(members.shape[1]), 2)))) + 1
        (lo_i, _), _ = jax.lax.scan(bs, (jnp.zeros((), jnp.int32), c), None, length=iters)
        return lo_i

    plen = jax.vmap(jax.vmap(prefix_len))(
        levels, off, cnt.astype(jnp.int32),
        jnp.broadcast_to(version[:, None], (Q, P)).astype(jnp.int32))
    plen = jnp.where(valid, plen, 0)                              # (Q, P)

    # blocked scan over candidate prefixes
    cum = jnp.cumsum(plen, axis=1)
    starts = cum - plen                                           # (Q, P) in candidate space
    total = cum[:, -1]
    shift = off - starts                                          # (Q, P)

    top_d = jnp.full((Q, k), INF, jnp.float32)
    top_i = jnp.full((Q, k), NO_EDGE, jnp.int32)

    n_run = jnp.minimum(max_blocks, (jnp.max(total) + block - 1) // block)

    # member ids are read from the table laid flat: its relayout is a fresh
    # buffer that the v5e compiler can place in on-chip memory for the whole
    # loop, where the 2-D argument may stay in HBM (an id read there costs
    # ~3x as much)
    Lv, W = members.shape
    if Lv * W >= 2**31:
        raise ValueError(f"a member table of {Lv} x {W} ids overflows an "
                         "int32 flat index")
    flat_members = members.reshape(-1)

    def body(carry):
        blk, top_d, top_i = carry
        pos = blk * block + jnp.arange(block)                     # (B,) candidate positions
        lvl_b, midx = _node_of_position(pos, starts, cum, levels, shift)
        ok = pos[None, :] < total[:, None]
        cand = flat_members[jnp.clip(lvl_b * W + midx, 0, Lv * W - 1)]  # (Q, B)
        cand_safe = jnp.where(ok, cand, 0)
        # exact predicate re-check on raw endpoints
        sel = iv.eval_predicate(pred_mask_bits, lo_attr[cand_safe], hi_attr[cand_safe],
                                ql[:, None], qh[:, None]) & ok
        if quantized:
            # gather code rows (1-2 bytes/component); distances are
            # approximate and the engine re-ranks the merged top-R
            cb = arrays["codes"][cand_safe].astype(jnp.float32)
            dist = (cq[:, None]
                    - 2.0 * jnp.einsum("qd,qbd->qb", wq, cb)
                    + arrays["code_sq_norm"][cand_safe])
        else:
            diff = vectors[cand_safe] - queries[:, None, :]
            dist = jnp.einsum("qbd,qbd->qb", diff, diff, precision=HIGHEST)
        dist = jnp.where(sel, dist, INF)
        cat_d = jnp.concatenate([top_d, dist], axis=1)
        cat_i = jnp.concatenate([top_i, jnp.where(sel, cand, NO_EDGE)], axis=1)
        neg, pos_k = jax.lax.top_k(-cat_d, k)
        return blk + 1, -neg, jnp.take_along_axis(cat_i, pos_k, 1)

    _, top_d, top_i = jax.lax.while_loop(
        lambda carry: carry[0] < n_run, body,
        (jnp.zeros((), jnp.int32), top_d, top_i))
    return top_i, top_d, total, n_run


# The host-facing exact-search API is QueryEngine (repro.core.engine) with
# route="flat"/"pruned"; this module keeps the jitted engines.
