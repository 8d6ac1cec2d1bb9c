"""Fused predicate + L2 + running top-k Pallas kernel.

One kernel call answers an exact filtered k-NN query batch: the TPU grid walks
corpus blocks sequentially (TPU grids execute in order), each step computes the
masked distance tile in VMEM and folds it into a persistent (Q, k) accumulator
that every grid step aliases (out block index 0) — the (Q, N) distance matrix
never exists, in VMEM or HBM. This is the §Perf-iteration-6 engine as a single
kernel: HBM traffic = corpus + queries + (Q, 2k) outputs.

Top-k inside the kernel is k rounds of (min, first-position, mask) over the
running (Q, k) list and the fresh (Q, BN) tile together — k is small (<=32)
and the VPU eats the compares; no sort network, no in-kernel gather (block
ids are contiguous, so a tile position *is* an id) and no lane-unaligned
concatenate, none of which Mosaic lowers. Ties prefer the running list,
then the lower tile position: the lower id wins, as in ``lax.top_k``.
Endpoints travel as 2-D ``(1, N)``/``(Q, 1)`` tiles (see
:mod:`repro.kernels.pairwise_l2`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import intervals as iv

from .pairwise_l2 import endpoint_tiles

NO_EDGE = -1
DEFAULT_BN = 1024


def _first_min(x, pos, width: int):
    """Row minimum of ``x`` and the first position holding it, as (Q, 1)."""
    m = jnp.min(x, axis=1, keepdims=True)
    at = jnp.min(jnp.where(x == m, pos, width), axis=1, keepdims=True)
    return m, at


def _kernel(q_ref, c_ref, lo_ref, hi_ref, ql_ref, qh_ref,
            outd_ref, outi_ref, *, mask: int, k: int, bn: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        outd_ref[...] = jnp.full(outd_ref.shape, jnp.inf, jnp.float32)
        outi_ref[...] = jnp.full(outi_ref.shape, NO_EDGE, jnp.int32)

    q = q_ref[...].astype(jnp.float32)                 # (Q, d)
    c = c_ref[...].astype(jnp.float32)                 # (BN, d)
    hi_prec = jax.lax.Precision.HIGHEST
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    cn = jax.lax.dot_general(jnp.ones((1, c.shape[1]), jnp.float32), c * c,
                             (((1,), (1,)), ((), ())), precision=hi_prec,
                             preferred_element_type=jnp.float32)   # (1, BN)
    dist = qn - 2.0 * jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())), precision=hi_prec,
        preferred_element_type=jnp.float32) + cn
    sel = iv.eval_predicate(mask, lo_ref[...], hi_ref[...],
                            ql_ref[...], qh_ref[...])
    dist = jnp.where(sel, dist, jnp.inf)

    run_d, run_i = outd_ref[...], outi_ref[...]        # (Q, k)
    Q = dist.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (Q, bn), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, k), 1)
    new_d = jnp.full((Q, k), jnp.inf, jnp.float32)
    new_i = jnp.full((Q, k), NO_EDGE, jnp.int32)
    for r in range(k):
        m_run, at_run = _first_min(run_d, col, k)
        m_blk, at_blk = _first_min(dist, pos, bn)
        from_run = m_run <= m_blk
        id_run = jnp.sum(jnp.where(col == at_run, run_i, 0), axis=1,
                         keepdims=True)
        pick_d = jnp.where(from_run, m_run, m_blk)
        pick_i = jnp.where(from_run, id_run, step * bn + at_blk)
        new_d = jnp.where(col == r, pick_d, new_d)
        new_i = jnp.where(col == r, pick_i, new_i)
        run_d = jnp.where(from_run & (col == at_run), jnp.inf, run_d)
        dist = jnp.where(~from_run & (pos == at_blk), jnp.inf, dist)
    outd_ref[...] = new_d
    outi_ref[...] = jnp.where(jnp.isfinite(new_d), new_i, NO_EDGE)


@functools.partial(jax.jit, static_argnames=("mask", "k", "bn", "interpret"))
def fused_topk_l2(queries, corpus, lo, hi, ql, qh, mask: int, k: int = 10,
                  bn: int = DEFAULT_BN, interpret: bool = False):
    """(Q, d) x (N, d) -> exact filtered ((Q, k) ids, (Q, k) sq-distances)."""
    Q, d = queries.shape
    N = corpus.shape[0]
    bn = min(bn, -(-N // 128) * 128)
    Np = -(-N // bn) * bn
    cpad = jnp.pad(corpus, ((0, Np - N), (0, 0)))
    lop, hip, qlc, qhc = endpoint_tiles(lo, hi, ql, qh, Q, Np)

    outd, outi = pl.pallas_call(
        functools.partial(_kernel, mask=mask, k=k, bn=bn),
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((Q, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((Q, 1), lambda i: (0, 0)),
            pl.BlockSpec((Q, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Q, k), lambda i: (0, 0)),   # all steps alias block 0
            pl.BlockSpec((Q, k), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Q, k), jnp.float32),
                   jax.ShapeDtypeStruct((Q, k), jnp.int32)],
        interpret=interpret,
    )(queries, cpad, lop, hip, qlc, qhc)
    return outi, outd
