"""What a traced run records besides the profile, and the context that the
per-layer metric readers (``bench/metrics/<name>.py``) read.

Host spans are the benchmark's own: ``HostSpans.install`` wraps the
server's ``step``, the engine's ``execute`` and ``plan`` and the system's
``submit`` on their instances, timing each call on the host clock and
writing it into the profile as a ``jax.profiler.TraceAnnotation``, so the
device's idle gaps can be put down to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import peaks, predicate, trace

WRAPPED = (("server", "step", "bench.step"),
           ("engine", "execute", "bench.execute"),
           ("engine", "plan", "bench.plan"),
           (None, "submit", "bench.submit"))


class HostSpans:
    def __init__(self):
        self.calls: Dict[str, List[Tuple[float, float]]] = {}
        self.batches: List[Tuple[float, int, np.ndarray, np.ndarray]] = []

    @contextlib.contextmanager
    def mark(self, name: str):
        """The window's annotation; the profile stops when it closes, so
        the drain after the window is not traced."""
        import jax
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            jax.profiler.stop_trace()

    def _wrap(self, obj, attr: str, name: str):
        import jax
        fn = getattr(obj, attr)
        calls = self.calls.setdefault(name, [])
        batches = self.batches if name == "bench.execute" else None

        def wrapped(*a, **kw):
            t = time.perf_counter()
            if batches is not None:
                req = a[0]
                batches.append((t, int(req.mask), np.asarray(req.qlo),
                                np.asarray(req.qhi)))
            try:
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **kw)
            finally:
                calls.append((t, time.perf_counter()))

        setattr(obj, attr, wrapped)

    def install(self, system) -> None:
        for owner, attr, name in WRAPPED:
            obj = system if owner is None else getattr(system, owner)
            self._wrap(obj, attr, name)

    def seconds(self, name: str, t0: float, t1: float) -> float:
        return sum(max(0.0, min(e, t1) - max(s, t0))
                   for s, e in self.calls.get(name, []))


@dataclasses.dataclass
class Context:
    """Everything a per-layer metric reader may read. Times in seconds
    unless named otherwise; ``served`` counts answers delivered inside the
    window."""
    window_s: float
    served: int
    host_s: Dict[str, float]
    late_ms: np.ndarray
    compiles: int
    busy_s: float
    needed_bytes: float
    needed_flops: float
    peaks: Dict[str, float]
    trace: Optional[trace.Trace]
    planes: List[str]
    lo_ns: float
    hi_ns: float
    breakdown: dict

    def least_s(self) -> float:
        """Least time the chip could take for the window's work: the rows
        a correct answer must read and score, at the published peaks."""
        return max(self.needed_bytes / self.peaks["hbm_bw"],
                   self.needed_flops / self.peaks["flops"])


def needed_work(spans: HostSpans, corpus, t0: float, t1: float
                ) -> Tuple[float, float]:
    """(bytes, flops) the window's engine calls need at the least.

    Bytes: each call must read every row that some query of the call keeps,
    once: |union of the kept rows| * d * 4. Flops: each query must score
    every row it keeps: kept rows * 2d (a dot product or a sum of squared
    differences). Both count the rows a correct exact answer needs, not
    what the program executes, so they read the same whatever implements
    the scan."""
    K = corpus.grid.shape[0]
    hist = np.zeros((K, K), np.int64)
    np.add.at(hist, (corpus.lo_pos, corpus.hi_pos), 1)
    a = corpus.grid[:, None]
    b = corpus.grid[None, :]
    d = corpus.vectors.shape[1]
    rows_union = rows_pairs = 0
    for t, mask, qlo, qhi in spans.batches:
        if not t0 <= t <= t1:
            continue
        cells = np.zeros((K, K), bool)
        for ql, qh in zip(qlo, qhi):
            keep = predicate.holds(mask, a, b, ql, qh)
            cells |= keep
            rows_pairs += int(hist[keep].sum())
        rows_union += int(hist[cells].sum())
    return rows_union * d * 4.0, rows_pairs * 2.0 * d


def context(win, spans: HostSpans, prof_dir: str, corpus, devices,
            dev: dict, compiles: int) -> Context:
    tr = trace.load(prof_dir)
    shutil.rmtree(prof_dir, ignore_errors=True)
    bounds = tr.annotation("bench.window")
    planes = [f"/device:{d.platform.upper()}:{d.id}" for d in devices]
    planes = [p for p in planes if p in tr.device]
    t0, t1 = win.t0, win.t1
    served = int(np.sum((win.answered >= t0) & (win.answered <= t1)
                        & ~win.rejected))
    host = {name.split(".", 1)[1]: spans.seconds(name, t0, t1)
            for name in spans.calls}
    sends = win.sends_in_window()
    nbytes, flops = needed_work(spans, corpus, t0, t1)
    try:
        pk = peaks.of(dev["kind"])
    except KeyError:
        if dev["platform"] == "tpu":
            raise
        pk = None
    lo, hi = bounds if bounds else (0.0, 0.0)
    busy_s, bd = 0.0, {}
    if bounds and planes:
        busy_s = float(np.mean([trace.busy_ns(tr, p, lo, hi)
                                for p in planes])) * 1e-9
        bd = {"device_ops": trace.top_ops(tr, planes, lo, hi),
              "idle_gaps": trace.gaps_by_host(tr, planes, lo, hi)}
    return Context(window_s=(hi - lo) * 1e-9,
                   served=served, host_s=host,
                   late_ms=win.late[sends] * 1e3, compiles=compiles,
                   busy_s=busy_s, needed_bytes=nbytes,
                   needed_flops=flops, peaks=pk, trace=tr, planes=planes,
                   lo_ns=lo, hi_ns=hi, breakdown=bd)
