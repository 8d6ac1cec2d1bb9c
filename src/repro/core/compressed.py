"""Compressed-scan execution: approximate top-R over quantized codes, then
an exact float32 re-rank of those R candidates.

The flat route's cost at scale is streaming the corpus; scanning the
quantized codes instead cuts the streamed bytes 4x (int8) / 2x (float16).
The scan produces an over-fetched candidate list (``rerank_k >= k``) whose
distances are approximate — quantization error plus, on the Pallas int8
path, query-side rounding — and :func:`exact_rerank` recomputes the true
float32 distances for just those R rows before the final ``top_k(k)``, so
end recall matches the exact scan for any candidate set that contains the
true neighbors (the ``rerank_k`` knob trades that containment probability
against re-rank cost; the default ``max(4k, 32)`` recovers recall@10 to
within 0.01 on the bench grids).

Two scan implementations share the math
``dist = (||q||^2 - 2 q.offset) - 2 (q*scale).code + sq_norm``:

* :func:`compressed_flat_topr` — a ``lax.scan`` over corpus blocks that
  dequantizes each code block *in registers/cache* (never materializing a
  float32 copy of the corpus) and carries a running top-R. This is the
  CPU/XLA path and the shape the TPU kernel tiles follow.
* :func:`repro.kernels.pairwise_l2_int8` via ``use_kernel=True`` — the
  Pallas MXU path with integer dot products; the engine funnels its (Q, N)
  output through :func:`topr_from_dists`.

The float32 corpus used by the re-rank stays **host-side**: the engine
gathers the R candidate rows with NumPy and ships only the (Q, R, d) slice
to the device, so the quantized path never stages the full float32 corpus
in accelerator memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import intervals as iv

NO_EDGE = -1
DEFAULT_BLOCK = 4096


@functools.partial(jax.jit, static_argnames=("mask", "rerank", "block"))
def compressed_flat_topr(codes_t, scale, offset, sq_norm, lo, hi,
                         queries, ql, qh, *, mask: int, rerank: int,
                         block: int = DEFAULT_BLOCK):
    """Masked approximate top-``rerank`` over a **(d, n) transposed**
    quantized code table. Returns ((Q, R) int32 ids, (Q, R) approx dists),
    ascending, NO_EDGE/+inf padded where fewer than R rows qualify.

    The transposed layout is load-bearing, not cosmetic: each block slice
    is a contiguous (d, blk) panel, so the skinny (Q, d) x (d, blk) matmul
    consumes it directly — on XLA CPU that is ~4-5x faster than contracting
    against strided (blk, d) row-major slices, and it is what lets the
    1-byte stream actually beat the float32 fused scan end to end. The
    engine stages this view once per store (``QueryEngine.store_dev``); the
    canonical (n, d) ``QuantizedStore.codes`` stays row-major for the
    gather paths (pruned / graph) and persistence."""
    d, n = codes_t.shape
    Q = queries.shape[0]
    R = min(int(rerank), n)
    blk = min(block, n)
    nb = -(-n // blk)
    pad = nb * blk - n
    if pad:
        codes_t = jnp.pad(codes_t, ((0, 0), (0, pad)))
        sq_norm = jnp.pad(sq_norm, (0, pad))
        # NaN endpoints fail every RR comparison -> pad rows never qualify
        lo = jnp.pad(lo, (0, pad), constant_values=jnp.nan)
        hi = jnp.pad(hi, (0, pad), constant_values=jnp.nan)
    q = queries.astype(jnp.float32)
    w = q * scale[None, :]                                   # (Q, d)
    cq = jnp.sum(q * q, axis=1) - 2.0 * (q @ offset)         # (Q,)
    arange_b = jnp.arange(blk, dtype=jnp.int32)

    def body(carry, i):
        top_d, top_i = carry
        start = i * blk
        cb = jax.lax.dynamic_slice_in_dim(codes_t, start, blk, 1)
        sb = jax.lax.dynamic_slice_in_dim(sq_norm, start, blk, 0)
        lb = jax.lax.dynamic_slice_in_dim(lo, start, blk, 0)
        hb = jax.lax.dynamic_slice_in_dim(hi, start, blk, 0)
        # dequant-free distance: the scale is already folded into w and the
        # offset into cq/sq_norm, so the code block is consumed at its
        # stored width — one (Q, blk) matmul against the cast panel
        dist = (cq[:, None] - 2.0 * (w @ cb.astype(jnp.float32))
                + sb[None, :])
        sel = iv.eval_predicate(mask, lb[None, :], hb[None, :],
                                ql[:, None], qh[:, None])
        dist = jnp.where(sel, dist, jnp.inf)
        ids = (start + arange_b)[None, :]
        cat_d = jnp.concatenate([top_d, dist], axis=1)
        cat_i = jnp.concatenate(
            [top_i, jnp.broadcast_to(ids, (Q, blk)).astype(jnp.int32)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, R)
        return (-neg, jnp.take_along_axis(cat_i, pos, 1)), None

    top0 = (jnp.full((Q, R), jnp.inf, jnp.float32),
            jnp.full((Q, R), NO_EDGE, jnp.int32))
    (top_d, top_i), _ = jax.lax.scan(body, top0, jnp.arange(nb))
    top_i = jnp.where(jnp.isfinite(top_d), top_i, NO_EDGE)
    return top_i, top_d


@functools.partial(jax.jit, static_argnames=("rerank",))
def topr_from_dists(dists, *, rerank: int):
    """Reduce a full (Q, N) approximate distance matrix (e.g. the Pallas
    int8 kernel output) to the (ids, dists) top-R candidate form."""
    R = min(int(rerank), dists.shape[1])
    neg, idx = jax.lax.top_k(-dists, R)
    ids = jnp.where(jnp.isfinite(neg), idx, NO_EDGE).astype(jnp.int32)
    return ids, -neg


@functools.partial(jax.jit, static_argnames=("k",))
def exact_rerank(queries, cand_vecs, cand_ids, *, k: int):
    """Exact float32 squared L2 over the gathered (Q, R, d) candidate rows,
    then ``top_k(k)``. NO_EDGE candidates rank +inf; ids whose re-ranked
    distance is +inf come back as NO_EDGE (fewer than k qualifiers)."""
    q = queries.astype(jnp.float32)
    diff = cand_vecs.astype(jnp.float32) - q[:, None, :]
    dist = jnp.einsum("qrd,qrd->qr", diff, diff,
                      precision=jax.lax.Precision.HIGHEST)
    dist = jnp.where(cand_ids >= 0, dist, jnp.inf)
    neg, pos = jax.lax.top_k(-dist, k)
    ids = jnp.where(jnp.isfinite(neg),
                    jnp.take_along_axis(cand_ids, pos, 1), NO_EDGE)
    return ids.astype(jnp.int32), -neg
