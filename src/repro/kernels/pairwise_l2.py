"""Fused RR-predicate + pairwise-L2 Pallas TPU kernel (DESIGN.md §2).

The paper's search cost is dominated by distance verification of candidates
that may or may not satisfy the filter. On TPU we fuse the two: each grid cell
loads a (BQ, d) query tile and a (BN, d) corpus tile into VMEM, forms
``|q|^2 - 2 q·cᵀ + |c|^2`` on the MXU with fp32 accumulation, evaluates the RR
predicate on the (1, BN)/(BQ, 1) endpoint tiles in VREGs and writes ``+inf`` for failing
candidates — non-qualifying vectors never leave the chip, the TPU analogue of
"avoid verifying vectors that do not satisfy the query predicate".

Block sizes are MXU-aligned (multiples of 128 on the N axis, 8 on Q); the
full feature depth d rides along the minor dimension (d <= ~4k keeps the
working set ~4 MB < VMEM). Endpoints travel as 2-D ``(1, N)`` rows and
``(Q, 1)`` columns: Mosaic tiles 1-D blocks differently from XLA's 1-D
layout, so a ``(bn,)`` block is refused on a real TPU. The cross term is
contracted at full float32 precision (``HIGHEST``), so the kernel ranks like
the exact jnp path instead of a single bf16 MXU pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import intervals as iv

DEFAULT_BQ = 128
DEFAULT_BN = 256


def _kernel(q_ref, c_ref, lo_ref, hi_ref, ql_ref, qh_ref, out_ref, *, mask: int):
    q = q_ref[...].astype(jnp.float32)          # (BQ, d)
    c = c_ref[...].astype(jnp.float32)          # (BN, d)
    qn = jnp.sum(q * q, axis=1, keepdims=True)  # (BQ, 1)
    # |c|^2 as a (1, BN) row: ones(1, d) x (BN, d)^T keeps it lane-major
    cn = jax.lax.dot_general(jnp.ones((1, c.shape[1]), jnp.float32), c * c,
                             (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    # MXU: (BQ, d) x (d, BN)
    cross = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    dist = qn - 2.0 * cross + cn
    sel = iv.eval_predicate(mask, lo_ref[...], hi_ref[...],  # (1, BN)
                            ql_ref[...], qh_ref[...])        # (BQ, 1)
    out_ref[...] = jnp.where(sel, dist, jnp.inf)


def block_sizes(Q: int, N: int, bq: int, bn: int):
    """Tile-aligned blocks and padded extents: ``bq`` a multiple of 8 and
    ``bn`` of 128 (each capped at the padded problem size), ``Qp``/``Np``
    multiples of them."""
    bq = min(bq, -(-Q // 8) * 8)
    bn = min(bn, -(-N // 128) * 128)
    return bq, bn, -(-Q // bq) * bq, -(-N // bn) * bn


def endpoint_tiles(lo, hi, ql, qh, Qp: int, Np: int):
    """Object endpoints as (1, Np) rows, query endpoints as (Qp, 1) columns,
    float32. NaN pads fail every RR comparison, so padded rows and queries
    never qualify."""
    def pad(a, n):
        a = a.astype(jnp.float32)
        return jnp.pad(a, (0, n - a.shape[0]), constant_values=jnp.nan)
    return (pad(lo, Np)[None, :], pad(hi, Np)[None, :],
            pad(ql, Qp)[:, None], pad(qh, Qp)[:, None])


@functools.partial(jax.jit, static_argnames=("mask", "bq", "bn", "interpret"))
def pairwise_l2_masked(queries, corpus, lo, hi, ql, qh, mask: int,
                       bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                       interpret: bool = False):
    """(Q, d) x (N, d) -> (Q, N) fused masked squared-L2. Q and N need not be
    block-aligned; inputs are padded and the pad region is predicate-masked."""
    Q, d = queries.shape
    N = corpus.shape[0]
    bq, bn, Qp, Np = block_sizes(Q, N, bq, bn)
    qpad = jnp.pad(queries, ((0, Qp - Q), (0, 0)))
    cpad = jnp.pad(corpus, ((0, Np - N), (0, 0)))
    lop, hip, qlp, qhp = endpoint_tiles(lo, hi, ql, qh, Qp, Np)

    grid = (Qp // bq, Np // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, mask=mask),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Np), jnp.float32),
        interpret=interpret,
    )(qpad, cpad, lop, hip, qlp, qhp)
    return out[:Q, :N]
