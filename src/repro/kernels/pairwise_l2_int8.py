"""Fused RR-predicate + int8 compressed-scan Pallas TPU kernel.

The float32 variant (:mod:`repro.kernels.pairwise_l2`) is bandwidth-bound:
each grid cell streams a (BN, d) float32 corpus tile from HBM. This variant
streams the *codes* instead — 4x fewer bytes per tile — and keeps the MXU on
the int8 path: the per-query weights ``w = q * scale`` are symmetric-
quantized to int8 on the host side of the call (``alpha`` per query), the
tile product is an int8 x int8 -> int32 ``dot_general``
(``preferred_element_type=jnp.int32``), and the dequantized correction

    dist ~= (||q||^2 - 2 q.offset) - 2 * alpha * (wq . code) + sq_norm

is applied in VREGs before the RR predicate writes ``+inf`` for failing
candidates. The only approximation beyond storage quantization is the
query-side rounding of ``w / alpha``; both are absorbed by the engine's
exact float32 re-rank of the top ``rerank_k`` candidates.

Block shapes and the 2-D endpoint layout follow the float32 kernel
(:func:`repro.kernels.pairwise_l2.block_sizes` / ``endpoint_tiles``); the
per-query terms ride as ``(Q, 1)`` columns and ``sq_norm`` as a ``(1, N)``
row. On a real TPU the int8 operands want the (32, 128) minimum tile, so the
query block is a multiple of 32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import intervals as iv

from .pairwise_l2 import block_sizes, endpoint_tiles
from .ref import quantize_query_weights_ref

DEFAULT_BQ = 128
DEFAULT_BN = 256


def _kernel(wq_ref, c_ref, alpha_ref, cq_ref, sqn_ref, lo_ref, hi_ref,
            ql_ref, qh_ref, out_ref, *, mask: int):
    wq = wq_ref[...]                            # (BQ, d) int8
    c = c_ref[...]                              # (BN, d) int8
    # MXU int8 path: (BQ, d) x (d, BN) with int32 accumulation
    acc = jax.lax.dot_general(wq, c, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    dist = (cq_ref[...]                         # (BQ, 1)
            - 2.0 * alpha_ref[...] * acc.astype(jnp.float32)
            + sqn_ref[...])                     # (1, BN)
    sel = iv.eval_predicate(mask, lo_ref[...], hi_ref[...],
                            ql_ref[...], qh_ref[...])
    out_ref[...] = jnp.where(sel, dist, jnp.inf)


@functools.partial(jax.jit, static_argnames=("mask", "bq", "bn", "interpret"))
def pairwise_l2_int8(queries, codes, scale, offset, sq_norm, lo, hi, ql, qh,
                     mask: int, bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
                     interpret: bool = False):
    """(Q, d) float32 queries x (N, d) int8 codes -> (Q, N) approximate
    masked squared-L2 against the dequantized corpus. Q and N need not be
    block-aligned; pad rows are zero codes masked by NaN endpoints."""
    Q, d = queries.shape
    N = codes.shape[0]
    wq, alpha, cq = quantize_query_weights_ref(queries, scale, offset)
    bq, bn, Qp, Np = block_sizes(-(-Q // 32) * 32, N, bq, bn)
    wqp = jnp.pad(wq, ((0, Qp - Q), (0, 0)))
    cpad = jnp.pad(codes, ((0, Np - N), (0, 0)))
    # alpha pads to 1 (a 0 divisor never happens; value is irrelevant —
    # padded rows/cols are predicate-masked via NaN endpoints below)
    alphap = jnp.pad(alpha, (0, Qp - Q), constant_values=1.0)[:, None]
    cqp = jnp.pad(cq, (0, Qp - Q))[:, None]
    sqnp = jnp.pad(sq_norm.astype(jnp.float32), (0, Np - N))[None, :]
    lop, hip, qlp, qhp = endpoint_tiles(lo, hi, ql, qh, Qp, Np)

    grid = (Qp // bq, Np // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, mask=mask),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Np), jnp.float32),
        interpret=interpret,
    )(wqp, cpad, alphap, cqp, sqnp, lop, hip, qlp, qhp)
    return out[:Q, :N]
