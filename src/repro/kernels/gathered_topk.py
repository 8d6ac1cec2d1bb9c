"""Fused gather + distance + label-mask + beam-merge Pallas kernel.

One kernel call executes the whole wavefront step of Algorithm 4's beam
search: for each query it gathers the candidate vectors by id from the corpus
table, computes squared L2, applies the label mask ``b <= version <= e``, and
folds the masked candidates into the sorted (pool_ids, pool_d, expanded) beam
— replacing the unfused gather → einsum → concat → ``top_k(L + F*S)`` chain
with a single call. Modeled on :mod:`repro.kernels.fused_topk`'s
running-accumulator design: the merge is L rounds of (min, argmin, mask) on
the VPU, which matches ``jax.lax.top_k``'s first-index tie-breaking exactly.

The corpus table is presented to every grid step whole (the gather indices
are per-query dynamic), so it must fit VMEM, which a real corpus does not:
on a TPU, :class:`repro.core.QueryEngine` refuses the graph route with
``use_kernel=True`` instead of calling this kernel. The CPU/test path runs
in interpret mode where the gather is a plain jnp take.
Inputs follow the search loop's conventions: ``avail`` marks candidates that
are structurally valid, unvisited, and first-occurrence (the loop computes
this against its packed visited bitmap); ids may be ``NO_EDGE`` where not
available.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NO_EDGE = -1
DEFAULT_BQ = 8


def _extract_pool(dist, ids, exp, L: int):
    """L rounds of min-extraction carrying (id, expanded) along; ties break on
    the first index, matching ``top_k(-dist)``. +inf slots yield
    (NO_EDGE, +inf, False) — the beam's empty-slot invariant."""
    out_d, out_i, out_e = [], [], []
    pos = jnp.arange(dist.shape[1])[None, :]
    for _ in range(L):
        m = jnp.min(dist, axis=1)                       # (BQ,)
        am = jnp.argmin(dist, axis=1)                   # (BQ,)
        out_d.append(m)
        out_i.append(jnp.take_along_axis(ids, am[:, None], 1)[:, 0])
        out_e.append(jnp.take_along_axis(exp, am[:, None], 1)[:, 0])
        dist = jnp.where(pos == am[:, None], jnp.inf, dist)
    d = jnp.stack(out_d, 1)                             # (BQ, L)
    i = jnp.stack(out_i, 1)
    e = jnp.stack(out_e, 1)
    fin = jnp.isfinite(d)
    return jnp.where(fin, i, NO_EDGE), d, jnp.where(fin, e, 0)


def _merge_step(q, cand, ids, ok, pid_ref, pd_ref, pexp_ref,
                oid_ref, od_ref, oexp_ref, L: int):
    """Shared epilogue of both table layouts: squared L2 of the gathered
    candidates, label mask, beam merge, write-back."""
    diff = cand - q[:, None, :]
    nd = jnp.sum(diff * diff, axis=-1)
    nd = jnp.where(ok, nd, jnp.inf)
    nid = jnp.where(ok, ids, NO_EDGE)

    cat_d = jnp.concatenate([pd_ref[...], nd], axis=1)
    cat_i = jnp.concatenate([pid_ref[...], nid], axis=1)
    cat_e = jnp.concatenate(
        [pexp_ref[...], jnp.zeros(nd.shape, pexp_ref.dtype)], axis=1)
    mi, md, me = _extract_pool(cat_d, cat_i, cat_e, L)
    oid_ref[...] = mi
    od_ref[...] = md
    oexp_ref[...] = me


def _kernel(q_ref, v_ref, ids_ref, avail_ref, b_ref, e_ref, ver_ref,
            pid_ref, pd_ref, pexp_ref, oid_ref, od_ref, oexp_ref, *, L: int):
    q = q_ref[...].astype(jnp.float32)                  # (BQ, d)
    table = v_ref[...].astype(jnp.float32)              # (n, d)
    ids = ids_ref[...]                                  # (BQ, M)
    ver = ver_ref[...]                                  # (BQ,)
    ok = ((avail_ref[...] != 0) & (b_ref[...] <= ver[:, None]) &
          (ver[:, None] <= e_ref[...]))
    idx = jnp.where(ids < 0, 0, ids)
    cand = table[idx]                                   # (BQ, M, d) gather
    _merge_step(q, cand, ids, ok, pid_ref, pd_ref, pexp_ref,
                oid_ref, od_ref, oexp_ref, L)


def _kernel_quant(q_ref, v_ref, sc_ref, of_ref, ids_ref, avail_ref, b_ref,
                  e_ref, ver_ref, pid_ref, pd_ref, pexp_ref,
                  oid_ref, od_ref, oexp_ref, *, L: int):
    """Quantized-table wavefront step: the gather pulls int8/float16 code
    rows (the bandwidth win — 4x/2x fewer bytes per candidate) and the
    affine dequantization ``code * scale + offset`` happens on the gathered
    (BQ, M, d) tile in VMEM, never on the full table."""
    q = q_ref[...].astype(jnp.float32)                  # (BQ, d)
    table = v_ref[...]                                  # (n, d) codes
    ids = ids_ref[...]                                  # (BQ, M)
    ver = ver_ref[...]                                  # (BQ,)
    ok = ((avail_ref[...] != 0) & (b_ref[...] <= ver[:, None]) &
          (ver[:, None] <= e_ref[...]))
    idx = jnp.where(ids < 0, 0, ids)
    cand = (table[idx].astype(jnp.float32) * sc_ref[...][None, None, :]
            + of_ref[...][None, None, :])               # (BQ, M, d)
    _merge_step(q, cand, ids, ok, pid_ref, pd_ref, pexp_ref,
                oid_ref, od_ref, oexp_ref, L)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gathered_topk(queries, vectors, ids, avail, b, e, version,
                  pool_ids, pool_d, pool_exp, bq: int = DEFAULT_BQ,
                  interpret: bool = False):
    """(Q, d) queries x (n, d) table x (Q, M) candidates x (Q, L) beam ->
    merged ((Q, L) ids, (Q, L) sq-dists, (Q, L) expanded-flags)."""
    Q, d = queries.shape
    M = ids.shape[1]
    L = pool_d.shape[1]
    bq = min(bq, Q) if Q else 1
    Qp = -(-Q // bq) * bq
    pad = Qp - Q

    def padq(a, fill=0):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=fill)

    exp_in = pool_exp.astype(jnp.int32)
    args = (padq(queries), jnp.asarray(vectors, jnp.float32),
            padq(ids.astype(jnp.int32), NO_EDGE),
            padq(avail.astype(jnp.int32)), padq(b.astype(jnp.int32)),
            padq(e.astype(jnp.int32)), padq(version.astype(jnp.int32)),
            padq(pool_ids.astype(jnp.int32), NO_EDGE),
            padq(pool_d.astype(jnp.float32), jnp.inf), padq(exp_in))
    n = vectors.shape[0]
    oid, od, oexp = pl.pallas_call(
        functools.partial(_kernel, L=L),
        grid=(Qp // bq,),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i: (i, 0)),
            pl.BlockSpec((n, d), lambda i: (0, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Qp, L), jnp.int32),
                   jax.ShapeDtypeStruct((Qp, L), jnp.float32),
                   jax.ShapeDtypeStruct((Qp, L), jnp.int32)],
        interpret=interpret,
    )(*args)
    return oid[:Q], od[:Q], oexp[:Q].astype(bool)


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gathered_topk_quant(queries, codes, scale, offset, ids, avail, b, e,
                        version, pool_ids, pool_d, pool_exp,
                        bq: int = DEFAULT_BQ, interpret: bool = False):
    """:func:`gathered_topk` over a quantized (n, d) code table (int8 or
    float16) with per-dimension affine dequant params ``scale``/``offset``
    (each (d,) float32). Distances are squared L2 against the dequantized
    rows ``code * scale + offset``."""
    Q, d = queries.shape
    M = ids.shape[1]
    L = pool_d.shape[1]
    bq = min(bq, Q) if Q else 1
    Qp = -(-Q // bq) * bq
    pad = Qp - Q

    def padq(a, fill=0):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=fill)

    exp_in = pool_exp.astype(jnp.int32)
    args = (padq(queries), jnp.asarray(codes),
            jnp.asarray(scale, jnp.float32), jnp.asarray(offset, jnp.float32),
            padq(ids.astype(jnp.int32), NO_EDGE),
            padq(avail.astype(jnp.int32)), padq(b.astype(jnp.int32)),
            padq(e.astype(jnp.int32)), padq(version.astype(jnp.int32)),
            padq(pool_ids.astype(jnp.int32), NO_EDGE),
            padq(pool_d.astype(jnp.float32), jnp.inf), padq(exp_in))
    n = codes.shape[0]
    oid, od, oexp = pl.pallas_call(
        functools.partial(_kernel_quant, L=L),
        grid=(Qp // bq,),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i: (i, 0)),
            pl.BlockSpec((n, d), lambda i: (0, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq, M), lambda i: (i, 0)),
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
            pl.BlockSpec((bq, L), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Qp, L), jnp.int32),
                   jax.ShapeDtypeStruct((Qp, L), jnp.float32),
                   jax.ShapeDtypeStruct((Qp, L), jnp.int32)],
        interpret=interpret,
    )(*args)
    return oid[:Q], od[:Q], oexp[:Q].astype(bool)
