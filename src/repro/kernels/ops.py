"""Public jit'd kernel entry points with automatic backend dispatch.

On TPU the Pallas kernels run compiled; everywhere else (this CPU container)
they run in ``interpret=True`` mode, which executes the kernel body in Python
on CPU — bitwise the same program structure, used by tests/benchmarks to
validate against the :mod:`repro.kernels.ref` oracles.

While tracing is on (a ``repro.obs`` tracer installed or a
``jax.profiler`` session recording), each entry point records a
``kernel:<name>`` span around its dispatch, annotated with ``bytes``: the
bytes the kernel streams by its byte model. The span does not wait for the
kernel: the device time is the profiler's to measure, and a traced run
dispatches exactly as an untraced one. Under ``jax.jit`` the span times the
trace, once per compile.

The byte models are *per kernel*, not a naive sum of input array sizes:
the gathered kernels read ``Q*M`` candidate rows out of the table (not the
whole table), and the compressed-scan kernels stream the int8/float16 code
bytes (not a float32-equivalent). They are exported
(:func:`pairwise_stream_bytes`, :func:`gathered_stream_bytes`) for
benchmarks that report side-by-side float32/int8 bandwidth.
"""
from __future__ import annotations

import functools

import jax

from repro import obs

from . import pairwise_l2 as _pw
from . import pairwise_l2_int8 as _pw8
from . import gathered_l2 as _gl
from . import ref


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _nbytes(*arrays) -> int:
    """Sum of input array bytes — the byte model for kernels that stream
    every input exactly once (the pairwise family)."""
    total = 0
    for a in arrays:
        nb = getattr(a, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def pairwise_stream_bytes(Q: int, N: int, d: int, itemsize: int) -> int:
    """Byte model of a full-table masked scan: the (N, d) table at its
    storage itemsize, float32 queries, per-row endpoints, per-query bounds.
    ``itemsize`` is the table's bytes per component (4 float32, 2 float16,
    1 int8) — the lever the compressed tier pulls."""
    return N * d * itemsize + Q * d * 4 + 2 * N * 4 + 2 * Q * 4


def gathered_stream_bytes(Q: int, M: int, L: int, d: int,
                          itemsize: int) -> int:
    """Byte model of one wavefront step: the gather touches ``Q*M``
    candidate rows of ``d*itemsize`` bytes each — NOT the whole (n, d)
    table — plus the per-candidate id/avail/label arrays and the (Q, L)
    beam state in and out."""
    return (Q * d * 4                   # queries
            + Q * M * d * itemsize      # gathered candidate rows
            + Q * M * (4 * 4)           # ids, avail, lab_b, lab_e (int32)
            + Q * 4                     # versions
            + 2 * Q * L * (4 + 4 + 4))  # beam pool in + out (ids, d, exp)


def _kernel_span(name: str, nbytes: int):
    """The span around one kernel's dispatch, carrying the bytes it streams
    by its byte model (:data:`repro.obs.NULL_SPAN` while tracing is off).
    It does not wait for the kernel."""
    return obs.span(name).set("bytes", int(nbytes))


def pairwise_l2_masked(queries, corpus, lo, hi, ql, qh, mask: int,
                       bq: int = _pw.DEFAULT_BQ, bn: int = _pw.DEFAULT_BN):
    Q, d = queries.shape
    nbytes = pairwise_stream_bytes(Q, corpus.shape[0], d,
                                   corpus.dtype.itemsize)
    with _kernel_span("kernel:pairwise_l2_masked", nbytes):
        return _pw.pairwise_l2_masked(queries, corpus, lo, hi, ql, qh, mask,
                                      bq=bq, bn=bn, interpret=_interpret())


def pairwise_l2_int8(queries, codes, scale, offset, sq_norm, lo, hi, ql, qh,
                     mask: int, bq: int = _pw8.DEFAULT_BQ,
                     bn: int = _pw8.DEFAULT_BN):
    """Compressed masked scan over int8 codes (integer MXU dot products +
    dequantized correction; :mod:`repro.kernels.pairwise_l2_int8`). The
    span's ``bytes`` count the *compressed* byte stream."""
    Q, d = queries.shape
    N = codes.shape[0]
    nbytes = (pairwise_stream_bytes(Q, N, d, 1)
              + N * 4                   # sq_norm
              + 2 * d * 4)              # scale + offset
    with _kernel_span("kernel:pairwise_l2_int8", nbytes):
        return _pw8.pairwise_l2_int8(queries, codes, scale, offset, sq_norm,
                                     lo, hi, ql, qh, mask, bq=bq, bn=bn,
                                     interpret=_interpret())


def gathered_l2(queries, cand_vecs, bq: int = _gl.DEFAULT_BQ):
    with _kernel_span("kernel:gathered_l2", _nbytes(queries, cand_vecs)):
        return _gl.gathered_l2(queries, cand_vecs, bq=bq,
                               interpret=_interpret())


def gathered_l2_dot(queries, cand_vecs, bq: int = _gl.DEFAULT_BQ):
    with _kernel_span("kernel:gathered_l2_dot", _nbytes(queries, cand_vecs)):
        return _gl.gathered_l2_dot(queries, cand_vecs, bq=bq,
                                   interpret=_interpret())


def gathered_topk(queries, vectors, ids, avail, b, e, version,
                  pool_ids, pool_d, pool_exp, bq: int = None):
    """Fused wavefront step: gather-by-id + L2 + label mask + beam merge
    (:mod:`repro.kernels.gathered_topk`) in one kernel call."""
    from . import gathered_topk as _gt
    Q, d = queries.shape
    nbytes = gathered_stream_bytes(Q, ids.shape[1], pool_d.shape[1], d,
                                   vectors.dtype.itemsize)
    with _kernel_span("kernel:gathered_topk", nbytes):
        return _gt.gathered_topk(
            queries, vectors, ids, avail, b, e, version, pool_ids, pool_d,
            pool_exp, bq=bq or _gt.DEFAULT_BQ, interpret=_interpret())


def gathered_topk_quant(queries, codes, scale, offset, ids, avail, b, e,
                        version, pool_ids, pool_d, pool_exp, bq: int = None):
    """Wavefront step over a quantized code table: the gather streams
    int8/float16 rows and dequantizes in VMEM
    (:func:`repro.kernels.gathered_topk.gathered_topk_quant`)."""
    from . import gathered_topk as _gt
    Q, d = queries.shape
    nbytes = (gathered_stream_bytes(Q, ids.shape[1], pool_d.shape[1], d,
                                    codes.dtype.itemsize)
              + 2 * d * 4)              # scale + offset
    with _kernel_span("kernel:gathered_topk_quant", nbytes):
        return _gt.gathered_topk_quant(
            queries, codes, scale, offset, ids, avail, b, e, version,
            pool_ids, pool_d, pool_exp, bq=bq or _gt.DEFAULT_BQ,
            interpret=_interpret())


# re-export oracles for convenience
pairwise_l2_masked_ref = ref.pairwise_l2_masked_ref
pairwise_l2_int8_ref = ref.pairwise_l2_int8_ref
gathered_l2_ref = ref.gathered_l2_ref
gathered_topk_ref = ref.gathered_topk_ref
gathered_topk_quant_ref = ref.gathered_topk_quant_ref


def fused_topk_l2(queries, corpus, lo, hi, ql, qh, mask: int, k: int = 10,
                  bn: int = 1024):
    from . import fused_topk as _ft
    Q, d = queries.shape
    nbytes = pairwise_stream_bytes(Q, corpus.shape[0], d,
                                   corpus.dtype.itemsize)
    with _kernel_span("kernel:fused_topk_l2", nbytes):
        return _ft.fused_topk_l2(queries, corpus, lo, hi, ql, qh, mask, k=k,
                                 bn=bn, interpret=_interpret())
