"""ShardedDeployment — multi-device serving of RRANN search.

The corpus partitions across the shards of a device mesh
(:func:`repro.launch.mesh.make_mesh`); each :class:`repro.core.SearchRequest`
fans out to every shard, runs the *existing* per-shard routes locally (the
exact pruned scan, the wavefront graph search, or a whole streaming
:class:`repro.streaming.SegmentedIndex` per shard), and the per-shard top-k
lists are merged on the host. Every shard's device work is dispatched
before any shard's answers are awaited, so the shards' devices run at once.
Only the fused :meth:`ShardedDeployment.flat` path merges on the devices.

Three shard layouts:

* :meth:`ShardedDeployment.build` — contiguous corpus slices, one
  :class:`repro.core.MSTGIndex` + :class:`repro.core.QueryEngine` per shard
  (every engine route available per shard; local ids are rebased to global
  row ids). Shard ``i``'s engine stages its arrays on, and runs on, the
  ``i``-th device of the mesh's corpus axis (of ``jax.devices()`` without a
  mesh, round-robin). The shards share one attribute domain, so the
  deployment plans a request once (:meth:`ShardedDeployment.plan`) and
  every shard executes that plan.
* :meth:`ShardedDeployment.from_segmented` — an existing
  :class:`repro.streaming.SegmentedIndex`'s frozen segments dealt round-robin
  onto shards (the delta buffer rides on shard 0). A snapshot view: segments
  are shared, not copied, so mutate the source index and re-derive.
* :meth:`ShardedDeployment.flat` — raw corpus slices served by the exact
  flat scan. The only layout with a fully *fused* device path: one
  ``shard_map`` call (:func:`repro.distributed.topk.sharded_flat_topk`)
  computes local scans and the merge with the
  :mod:`repro.distributed.topk` schedules (``all_gather`` for small meshes,
  ``tournament`` ppermute for pod-scale ones) without ever materializing
  per-shard results on host — this is what the ``--scale`` bench lane
  measures.

Fan-in width: ``DeploymentSpec.per_shard_k`` caps how many candidates each
shard contributes to the merge. ``k' == k`` reproduces the single-device
answer exactly (every global top-k member lives in some shard's local
top-k); ``k' < k`` trades recall for merge traffic (bytes ∝ D·Q·k') — the
recall-QPS pareto knob the scale bench sweeps.

Fault handling: a shard marked failed (:meth:`fail`) or raising
mid-search contributes only sentinel rows; a shard that raised is sent the
next request again, and :meth:`restore` brings back a failed one. The
request still answers — a degraded-recall :class:`repro.core.SearchResult`
with the lost shards in ``report.missing_shards`` and
``result.degraded == True`` — never an error.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax

from repro import obs
from repro.core import intervals as iv
from repro.core.api import (IndexSpec, RouteReport, SearchRequest,
                            SearchResult, ShardReport)
from repro.core.engine import EngineConfig, QueryEngine
from repro.core.flat import flat_search
from repro.core.hnsw import NO_EDGE
from repro.core.mstg import MSTGIndex
from repro.core.parallel import pool_size, run_build_pool

from .topk import resolve_merge, sharded_flat_topk

_MERGES = ("auto", "all_gather", "tournament", "host")


@dataclasses.dataclass(frozen=True)
class DeploymentSpec:
    """How a corpus deploys across shards — the distributed counterpart of
    :class:`repro.core.EngineConfig` (which it carries, one per-shard copy).

    Parameters
    ----------
    n_shards : int
        Shard count. Device merge schedules additionally need a mesh whose
        ``corpus_axis`` has exactly this size.
    corpus_axis : str
        Mesh axis the corpus partitions over.
    merge : str
        ``all_gather`` | ``tournament`` | ``host`` | ``auto``: the merge of
        the fused :meth:`ShardedDeployment.flat` path. ``auto`` resolves to
        ``host`` without a mesh, ``all_gather`` for D <= 8, and
        ``tournament`` for power-of-two D > 8. Every other layout merges
        its shards' answers on the host, and takes only ``auto`` or
        ``host``.
    per_shard_k : int
        Per-shard fan-in width k' (0 = the request's full k). ``k' == k`` is
        exact relative to single-device; smaller trades recall for merge
        bytes.
    engine : EngineConfig
        Config for every per-shard :class:`repro.core.QueryEngine`. This
        includes the quantized storage tier: ``EngineConfig(
        storage_dtype="int8", ...)`` gives every shard its own compressed
        code layout (each shard quantizes its corpus slice with its own
        per-dimension scales) plus the exact per-shard re-rank; the fused
        :meth:`ShardedDeployment.flat` layout is separate and always
        float32.
    index : IndexSpec, optional
        Build spec for :meth:`ShardedDeployment.build` shards (default
        ``IndexSpec()``).
    build_workers : int
        Process-pool width for :meth:`ShardedDeployment.build` — shard
        builds are independent, so ``build_workers > 1`` constructs them
        concurrently in spawn workers (each streams its own rate-limited
        build progress; the parent aggregates one pool line per finished
        shard). ``0``/``1`` = serial. An execution resource, not index
        state: it never changes the built shards, only the wall clock, and
        the pool degrades to the serial loop on platforms without process
        support.
    """

    n_shards: int = 1
    corpus_axis: str = "data"
    merge: str = "auto"
    per_shard_k: int = 0
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    index: Optional[IndexSpec] = None
    build_workers: int = 0

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.build_workers < 0:
            raise ValueError("build_workers must be >= 0 (0 = serial)")
        if self.merge not in _MERGES:
            raise ValueError(f"merge must be one of {_MERGES}, got "
                             f"{self.merge!r}")
        if self.per_shard_k < 0:
            raise ValueError("per_shard_k must be >= 0 (0 = full k)")
        if not isinstance(self.engine, EngineConfig):
            raise TypeError("engine must be an EngineConfig")

    def replace(self, **overrides) -> "DeploymentSpec":
        return dataclasses.replace(self, **overrides)


@dataclasses.dataclass
class _Shard:
    """One shard's serving state: a local engine plus the id rebase."""

    name: str
    engine: object                 # QueryEngine | SegmentedIndex | None(flat)
    n: int
    id_offset: Optional[int]       # local row -> global id shift; None = the
    #                                engine already returns external ids
    device: Any = None             # where the engine's arrays live (None =
    #                                JAX's default device)


def _shard_build_task(args):
    """Module-level worker body for parallel shard builds (spawn-context
    pools need a picklable top-level callable). Ships the finished index
    back as its save payload — plain numpy arrays + a meta dict — rather
    than the live object, and reports the in-worker build seconds so the
    parent can attribute wall clock per shard."""
    i, ispec, vectors, lo, hi, domain = args
    t0 = time.perf_counter()
    idx = MSTGIndex.build(ispec, vectors, lo, hi, domain=domain)
    arrays, meta = idx.to_payload()
    return i, arrays, meta, time.perf_counter() - t0


def _shard_devices(mesh, axis: str, n_shards: int) -> list:
    """One device per shard: along ``axis`` of ``mesh`` when one is
    attached, else ``jax.devices()`` round-robin."""
    if mesh is not None:
        devs = np.moveaxis(np.asarray(mesh.devices),
                           mesh.axis_names.index(axis), 0)
        return list(devs.reshape(devs.shape[0], -1)[:n_shards, 0])
    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(n_shards)]


def _host_merge(ids: np.ndarray, dists: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Merge stacked (D, Q, k') lists on host, shard-major like all_gather."""
    D, Q, w = ids.shape
    flat_i = np.moveaxis(ids, 0, 1).reshape(Q, D * w)
    flat_d = np.moveaxis(dists, 0, 1).reshape(Q, D * w)
    order = np.argsort(flat_d, axis=1, kind="stable")[:, :k]
    gi = np.take_along_axis(flat_i, order, 1)
    gd = np.take_along_axis(flat_d, order, 1)
    if gi.shape[1] < k:
        pad = [(0, 0), (0, k - gi.shape[1])]
        gi = np.pad(gi, pad, constant_values=NO_EDGE)
        gd = np.pad(gd, pad, constant_values=np.inf)
    return gi.astype(np.int64), gd.astype(np.float32)


class ShardedDeployment:
    """Serve one logical corpus from many shards (see module docstring).

    The declarative surface matches :class:`repro.core.QueryEngine`:
    ``execute(SearchRequest) -> SearchResult`` (and ``search`` as an alias),
    so a deployment drops into :class:`repro.serving.RetrievalServer`
    unchanged. ``result.report.route == "sharded"`` with one
    :class:`repro.core.ShardReport` per shard.
    """

    def __init__(self, shards: Sequence[_Shard], spec: DeploymentSpec,
                 mesh=None, *, _flat_arrays=None):
        if len(shards) != spec.n_shards:
            raise ValueError(f"{len(shards)} shards built but spec.n_shards "
                             f"= {spec.n_shards}")
        if mesh is not None and mesh.shape[spec.corpus_axis] != spec.n_shards:
            raise ValueError(
                f"mesh axis {spec.corpus_axis!r} has size "
                f"{mesh.shape[spec.corpus_axis]} but the deployment has "
                f"{spec.n_shards} shards")
        self.shards = list(shards)
        self.spec = spec
        self.mesh = mesh
        self._flat = _flat_arrays      # (corpus, lo, hi) for the fused path
        # the engine whose planner serves every shard (build(): one domain)
        self._planner: Optional[QueryEngine] = None
        self._failed: set = set()
        self.build_report: Optional[dict] = None
        calls = obs.get_registry().counter(
            "deployment_shard_calls_total",
            "Engine calls dispatched to each shard of a deployment",
            labels=("shard",))
        self._m_calls = [calls.labels(shard=str(i))
                         for i in range(len(self.shards))]

    # ---- constructors ----
    @classmethod
    def build(cls, vectors, lo, hi, *, spec: Optional[DeploymentSpec] = None,
              mesh=None) -> "ShardedDeployment":
        """Partition rows into ``n_shards`` contiguous slices and build one
        MSTG index + engine per slice. Result ids are global row indices.

        ``spec.build_workers > 1`` builds the shards in a spawn process
        pool (shard builds share nothing); the pool degrades to the serial
        loop when process pools are unavailable. Either way the deployment
        carries a ``build_report`` dict — pool size, wall seconds, per-shard
        build seconds, rows/sec — for bench attribution."""
        spec = spec or DeploymentSpec()
        _check_host_merge(spec)
        vectors = np.ascontiguousarray(vectors, np.float32)
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)
        ispec = spec.index or IndexSpec()
        n = vectors.shape[0]
        # one domain over the whole corpus: one plan is valid on every shard
        domain = iv.AttributeDomain.from_ranges(lo, hi)
        bounds = np.linspace(0, n, spec.n_shards + 1, dtype=np.int64)
        slices = [(int(bounds[i]), int(bounds[i + 1]))
                  for i in range(spec.n_shards)]
        t_wall = time.perf_counter()
        shard_secs: List[float] = []
        indexes: List[MSTGIndex] = []
        results = run_build_pool(
            _shard_build_task,
            [(i, ispec, vectors[a:b], lo[a:b], hi[a:b], domain)
             for i, (a, b) in enumerate(slices)],
            workers=spec.build_workers, label="shard")
        if results is not None:
            for _i, arrays, meta, secs in results:
                indexes.append(MSTGIndex.from_payload(arrays, meta))
                shard_secs.append(float(secs))
        else:
            for a, b in slices:
                t0 = time.perf_counter()
                indexes.append(MSTGIndex.build(ispec, vectors[a:b], lo[a:b],
                                               hi[a:b], domain=domain))
                shard_secs.append(time.perf_counter() - t0)
        shards = []
        for i, (idx, (a, b), dev) in enumerate(zip(
                indexes, slices,
                _shard_devices(mesh, spec.corpus_axis, spec.n_shards))):
            with jax.default_device(dev):
                engine = QueryEngine(idx, config=spec.engine)
            shards.append(_Shard(f"shard-{i}", engine, b - a, a, dev))
        wall = time.perf_counter() - t_wall
        self = cls(shards, spec, mesh)
        self._planner = shards[0].engine
        self.build_report = {
            # 0 when the pool fell back to the serial loop
            "pool_size": (pool_size(spec.build_workers, spec.n_shards)
                          if results is not None else 0),
            "wall_s": wall,
            "shard_seconds": shard_secs,
            "rows_per_sec": n / wall if wall > 0 else 0.0,
        }
        return self

    @classmethod
    def from_segmented(cls, segmented, *,
                       spec: Optional[DeploymentSpec] = None,
                       mesh=None) -> "ShardedDeployment":
        """Deal an existing SegmentedIndex's frozen segments round-robin onto
        shards (delta buffer on shard 0). Segments are shared with the
        source, not copied — a snapshot view; re-derive after mutations."""
        from repro.streaming.segmented import SegmentedIndex
        spec = spec or DeploymentSpec()
        _check_host_merge(spec)
        shards = []
        for i in range(spec.n_shards):
            view = SegmentedIndex(segmented.spec, policy=segmented.policy,
                                  engine_config=spec.engine)
            shards.append(_Shard(f"shard-{i}", view, 0, None))
        for j, seg in enumerate(segmented.segments):
            shards[j % spec.n_shards].engine.segments.append(seg)
        shards[0].engine.delta = segmented.delta
        for s in shards:
            s.n = len(s.engine)        # live rows: tombstones excluded
        return cls(shards, spec, mesh)

    @classmethod
    def flat(cls, vectors, lo, hi, *, spec: Optional[DeploymentSpec] = None,
             mesh=None) -> "ShardedDeployment":
        """Exact-scan shards over raw corpus slices. With a mesh and a device
        merge schedule the whole fan-out runs as ONE fused shard_map call
        (local scan + collective merge, nothing per-shard on host)."""
        spec = spec or DeploymentSpec()
        vectors = np.ascontiguousarray(vectors, np.float32)
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)
        n = vectors.shape[0]
        if n % spec.n_shards:
            raise ValueError(f"flat deployment needs corpus size ({n}) "
                             f"divisible by n_shards ({spec.n_shards})")
        nloc = n // spec.n_shards
        shards = [_Shard(f"shard-{i}", None, nloc, i * nloc)
                  for i in range(spec.n_shards)]
        return cls(shards, spec, mesh, _flat_arrays=(vectors, lo, hi))

    # ---- fault injection / liveness ----
    def fail(self, shard: int) -> None:
        """Mark a shard down (fleet-controller stand-in). Requests keep
        answering, degraded."""
        self._failed.add(int(shard))

    def restore(self, shard: int) -> None:
        self._failed.discard(int(shard))

    def _alive(self) -> np.ndarray:
        """(D,) bool — shards marked failed are down."""
        return np.array([i not in self._failed
                         for i in range(len(self.shards))], bool)

    # ---- planning ----
    def plan(self, mask: int, qlo: np.ndarray, qhi: np.ndarray
             ) -> List[iv.PlanSlot]:
        """The Theorem 4.1 plan of a batch, by the planner
        :meth:`repro.core.QueryEngine.plan` runs; one plan serves every
        shard of a :meth:`build` deployment, whose shards share one
        attribute domain."""
        if self._planner is None:
            raise ValueError("only a ShardedDeployment.build deployment "
                             "plans: its shards share one attribute domain")
        return self._planner.plan(mask, qlo, qhi)

    # ---- execution ----
    def execute(self, request: SearchRequest) -> SearchResult:
        """Fan one request out over the shards and merge. With
        ``request.trace=True`` the deployment owns the root trace — each
        shard's engine dispatch nests under its ``shard`` span — and the
        finished :class:`repro.obs.Trace` rides back on
        ``SearchResult.trace``."""
        if not isinstance(request, SearchRequest):
            raise TypeError("ShardedDeployment serves the declarative API "
                            "only; pass a repro.core.SearchRequest")
        tracer = obs.begin_request_trace() if request.trace else None
        try:
            with obs.span("sharded_search") as root:
                root.set("Q", len(request)).set("k", request.k)
                root.set("shards", self.spec.n_shards)
                result = self._execute_sharded(request)
        finally:
            trace = obs.end_request_trace(tracer)
        if trace is not None:
            result = dataclasses.replace(result, trace=trace)
        return result

    def _execute_sharded(self, request: SearchRequest) -> SearchResult:
        D, Q, k = self.spec.n_shards, len(request), request.k
        fused = (self._flat is not None and self.mesh is not None
                 and self.spec.merge != "host")
        with obs.span("plan") as psp:
            k_loc = min(self.spec.per_shard_k, k) if self.spec.per_shard_k \
                else k
            merge = resolve_merge(self.spec.merge, D) if fused else "host"
            alive = self._alive()
            psp.set("merge", merge).set("k_loc", k_loc)
            psp.set("alive", int(alive.sum()))
            slots = None
            if (self._planner is not None and Q
                    and (request.route or self.spec.engine.route) != "flat"):
                try:
                    slots = self.plan(request.mask, request.qlo, request.qhi)
                    psp.set("slots", len(slots))
                except ValueError:
                    pass     # each shard plans, and fails, on its own
        if fused:
            return self._execute_flat_fused(request, k_loc, merge, alive)

        ids = np.full((D, Q, k_loc), NO_EDGE, np.int64)
        dists = np.full((D, Q, k_loc), np.inf, np.float32)
        reports: List[Optional[ShardReport]] = [None] * D
        variants: List[str] = []
        # every live shard's device work starts before any answer is awaited
        pending = []
        for i, shard in enumerate(self.shards):
            if not alive[i]:
                reports[i] = ShardReport(shard=i, n=shard.n, route="lost",
                                         alive=False, k_fetched=0)
                continue
            self._m_calls[i].inc()
            t0 = time.perf_counter()
            with obs.span("shard") as ssp:
                ssp.set("shard", i).set("rows", Q).set("n", shard.n)
                try:
                    finish, n_slots = self._dispatch_shard(shard, request,
                                                           k_loc, slots)
                except Exception:
                    # a shard raising mid-search is a lost shard, not a lost
                    # request: sentinel rows, flagged, never re-raised
                    ssp.set("alive", False)
                    reports[i] = _error_report(i, shard)
                    continue
                ssp.set("slots", n_slots)
            pending.append((i, shard, t0, finish))
        with obs.span("fetch"):
            for i, shard, t0, finish in pending:
                try:
                    li, ld, rep = finish()
                except Exception:
                    reports[i] = _error_report(i, shard)
                    continue
                ids[i], dists[i] = self._local_answer(shard, li, ld, k_loc)
                if rep:
                    variants.extend(rep.variants)
                reports[i] = ShardReport(
                    shard=i, n=shard.n, route=rep.route if rep else "flat",
                    k_fetched=k_loc, latency_s=time.perf_counter() - t0,
                    slot_count=rep.slot_count if rep else 0)
        with obs.span("merge") as msp:
            msp.set("schedule", merge).set("rows", ids.size)
            gi, gd = _host_merge(ids, dists, k)
        report = RouteReport(
            route="sharded", requested=request.route or "auto",
            est_selectivity=None,
            slot_count=sum(r.slot_count for r in reports),
            variants=tuple(variants), shards=tuple(reports),
            missing_shards=tuple(i for i, r in enumerate(reports)
                                 if not r.alive),
            merge=merge)
        return SearchResult(gi, gd, report)

    # QueryEngine-compatible alias (RetrievalServer & co).
    def search(self, request: SearchRequest) -> SearchResult:
        return self.execute(request)

    def _dispatch_shard(self, shard: _Shard, request: SearchRequest,
                        k_loc: int, slots: Optional[List[iv.PlanSlot]]):
        """Start one shard's local answer on its device. Returns
        ``(finish, slots)``: ``finish()`` waits for the answer and gives
        ``(ids, dists, report)`` in the shard's own row ids (``report`` None
        for the flat layout); ``slots`` counts the plan slots it runs."""
        if shard.engine is None:      # flat layout, host-merged path
            corpus, lo, hi = self._flat
            a = shard.id_offset
            b = a + shard.n
            li, ld = flat_search(
                corpus[a:b], lo[a:b], hi[a:b], request.vectors,
                request.qlo.astype(np.float32), request.qhi.astype(np.float32),
                mask=request.mask, k=min(k_loc, shard.n),
                use_kernel=self.spec.engine.use_kernel)
            return (lambda: (li, ld, None)), 0
        # the graph route's beam pool is ef wide; keep ef >= k' so the
        # narrowed fan-in never truncates below the requested width
        local = dataclasses.replace(request, k=min(k_loc, max(shard.n, 1)),
                                    ef=max(request.ef, k_loc))
        with jax.default_device(shard.device):
            if isinstance(shard.engine, QueryEngine):
                p = shard.engine.dispatch(local, slots=slots)

                def finish():
                    res = shard.engine.collect(p)
                    return res.ids, res.dists, res.report
                return finish, len(p.slots)
            res = shard.engine.execute(local)
        return (lambda: (res.ids, res.dists, res.report)), \
            res.report.slot_count

    @staticmethod
    def _local_answer(shard: _Shard, li, ld, k_loc: int):
        """One shard's answer as (Q, k_loc) global-id arrays."""
        li = np.asarray(li, np.int64)
        ld = np.asarray(ld, np.float32)
        if li.shape[1] < k_loc:      # tiny shard: pad to the uniform width
            pad = [(0, 0), (0, k_loc - li.shape[1])]
            li = np.pad(li, pad, constant_values=NO_EDGE)
            ld = np.pad(ld, pad, constant_values=np.inf)
        if shard.id_offset is not None:
            li = np.where(li >= 0, li + shard.id_offset, np.int64(NO_EDGE))
        return li, ld

    def _execute_flat_fused(self, request: SearchRequest, k_loc: int,
                            merge: str, alive: np.ndarray) -> SearchResult:
        """The flat layout's one-call device path: shard-local exact scans
        and the collective merge fused into a single shard_map program."""
        corpus, lo, hi = self._flat
        t0 = time.perf_counter()
        with obs.span("fused_scan") as fsp:
            fsp.set("merge", merge).set("shards", len(self.shards))
            gi, gd = sharded_flat_topk(
                self.mesh, corpus, lo, hi, request.vectors,
                request.qlo.astype(np.float32), request.qhi.astype(np.float32),
                mask=request.mask, k=request.k,
                corpus_axis=self.spec.corpus_axis, merge=merge,
                per_shard_k=k_loc if k_loc < request.k else 0, alive=alive,
                use_kernel=self.spec.engine.use_kernel)
            gi = np.asarray(gi, np.int64)
            gd = np.asarray(gd, np.float32)
        lat = time.perf_counter() - t0
        reports = tuple(
            ShardReport(shard=i, n=s.n,
                        route="flat" if alive[i] else "lost",
                        alive=bool(alive[i]),
                        k_fetched=k_loc if alive[i] else 0,
                        latency_s=lat / len(self.shards))
            for i, s in enumerate(self.shards))
        missing = tuple(int(i) for i in np.flatnonzero(~alive))
        report = RouteReport(
            route="sharded", requested=request.route or "auto",
            est_selectivity=None, slot_count=0, variants=(),
            shards=reports, missing_shards=missing, merge=merge)
        return SearchResult(gi, gd, report)


def _check_host_merge(spec: DeploymentSpec) -> None:
    if spec.merge not in ("auto", "host"):
        raise ValueError(f"merge={spec.merge!r} is a schedule of the fused "
                         "flat layout; this layout merges on the host "
                         "(merge='auto' or 'host')")


def _error_report(i: int, shard: _Shard) -> ShardReport:
    return ShardReport(shard=i, n=shard.n, route="error", alive=False,
                       k_fetched=0)
