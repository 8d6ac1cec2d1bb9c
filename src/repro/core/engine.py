"""QueryEngine — the unified execution facade over a built MSTG index.

The canonical entry point is the declarative one::

    result = engine.search(SearchRequest(vectors, (qlo, qhi),
                                         Overlaps() | Before(), k=10))
    result.ids, result.dists, result.valid_mask, result.report

One object owns everything a request needs:

* **device staging** — graph arrays (:class:`repro.core.search.DeviceVariant`)
  and the pruned-scan member arrays are staged exactly once and shared by
  every path;
* **plan execution** — a batch is planned with the vectorized Theorem 4.1
  planner (:func:`repro.core.intervals.plan_batch_ranked`), every task slot is
  executed on its variant, and slot results are merged with
  :func:`repro.core.search.merge_topk`;
* **routing** — ``route="auto"`` estimates predicate selectivity *before any
  device work* from an O(1)-per-query exact rank-prefix table over a fixed
  corpus sample (:class:`repro.core.intervals.SelectivityIndex`; additionally
  memoized per ``(mask, rank-quantized query range)``) and sends
  low-selectivity batches to the exact pruned scan (work ∝ selectivity,
  recall 1.0) and everything else to the wavefront beam search — an
  auto-routed request executes the identical plan as pinning the route it
  selects;
* **wavefront execution** — the graph route resolves ``fanout`` (backend
  heuristic), skips plan slots whose tasks are all empty before dispatch,
  and chunks large batches through
  :func:`repro.core.search.mstg_graph_search_chunked` so converged queries
  are compacted out of the active batch between step slices;
* **jit-cache reuse** — query batches are padded up to power-of-two buckets so
  a serving process sees one trace per (mask, route, k, ef, bucket) instead of
  one per distinct batch size; padded queries carry empty tasks and cost no
  search steps.

Engine-lifetime tuning lives in one typed :class:`EngineConfig` dataclass
(``QueryEngine(index, config=EngineConfig(...))``); per-request knobs live on
the :class:`repro.core.api.SearchRequest`. When both speak to the same knob
the precedence is deterministic and uniform:

    **request wins over config wins over backend heuristic.**

Concretely: ``route`` resolves request → config; ``fanout`` and ``chunk``
resolve request → config → backend heuristic (TPU/CPU frontier width, batch
width chunking); ``ef``/``k``/``max_steps`` are request-only; ``use_kernel``/
``packed_visited``/routing-model constants are config-only.

Every execution returns a :class:`repro.core.api.SearchResult` whose
:class:`repro.core.api.RouteReport` records the chosen route, estimated
selectivity, plan slots, and selectivity-cache traffic. The tuple-era
positional call ``search(queries, qlo, qhi, mask)`` and the
``MSTGSearcher``/``FlatSearcher`` wrappers (deprecated since PR 2) were
removed in PR 6 — see the README migration guide. Bare constructor knobs
(``QueryEngine(index, use_kernel=True)``) still work but are deprecated
shims that warn once and fold into an :class:`EngineConfig`.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
import warnings
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs

from . import intervals as iv
from .api import RouteReport, SearchRequest, SearchResult
from .compressed import compressed_flat_topr, exact_rerank, topr_from_dists
from .flat import _pruned_search_variant, flat_search
from .hnsw import NO_EDGE
from .mstg import MSTGIndex
from .quant import QuantizedStore, check_storage_dtype, maybe_quantize
from .predicates import as_mask
from .search import (DeviceVariant, merge_topk, mstg_graph_search,
                     mstg_graph_search_chunked)

ROUTE_AUTO = "auto"
ROUTE_GRAPH = "graph"
ROUTE_PRUNED = "pruned"
ROUTE_FLAT = "flat"
_ROUTES = (ROUTE_AUTO, ROUTE_GRAPH, ROUTE_PRUNED, ROUTE_FLAT)
# kinds of engine_pruned_rows_total, in the column order of _scan_rows
_ROW_KINDS = ("needed", "to_longest", "scanned", "bound")


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _backend() -> str:
    """The JAX platform the engine's arrays and programs live on."""
    import jax
    return jax.default_backend()


# Deprecated shims (today: bare QueryEngine constructor knobs) warn exactly
# once per process per shim: serving loops that still cross a shim don't spam
# one warning per request, while the first crossing is always visible (and
# fails CI, which escalates DeprecationWarnings attributed to repro.* modules
# to errors).
_DEPRECATION_EMITTED: set = set()


def _warn_deprecated(key: str, message: str, *, stacklevel: int = 2) -> None:
    """Emit ``message`` as a DeprecationWarning once per process per ``key``,
    attributed to the shim's *caller* (``stacklevel`` counts from the shim
    function's own frame, exactly like a direct ``warnings.warn``)."""
    if key in _DEPRECATION_EMITTED:
        return
    _DEPRECATION_EMITTED.add(key)
    warnings.warn(message, DeprecationWarning, stacklevel=stacklevel + 1)


def reset_deprecation_warnings() -> None:
    """Forget which deprecation warnings already fired (test isolation)."""
    _DEPRECATION_EMITTED.clear()


def _empty_result(Q: int, k: int) -> Tuple[np.ndarray, np.ndarray]:
    return (np.full((Q, k), NO_EDGE, np.int32),
            np.full((Q, k), np.inf, np.float32))


def _scan_rows(scans: List[tuple]) -> np.ndarray:
    """Candidate rows of each pruned slot scan, (slots, 4) int64 in
    :data:`_ROW_KINDS` order: *needed*, the sum of the queries' candidate
    prefixes; *to_longest*, the batch's longest prefix in whole blocks, for
    every query (Qp x that x block), recounted here from the prefixes;
    *scanned*, what the loop ran (Qp x n_run x block); *bound*, its static
    cap (Qp x max_blocks x block), what a loop of fixed length would run.
    ``scans`` holds ``(total, n_run, max_blocks, block)`` per slot,
    ``total`` the scan's (Qp,) device array of prefix lengths and ``n_run``
    its device scalar of blocks run; every slot's come back in one fetch."""
    fetched = jax.device_get([(t, r) for t, r, _, _ in scans])
    out = np.zeros((len(scans), len(_ROW_KINDS)), np.int64)
    for i, ((tot, n_run), (*_, max_blocks, block)) in enumerate(
            zip(fetched, scans)):
        tot = np.asarray(tot, np.int64)
        longest = -(-int(tot.max(initial=0)) // block)
        out[i] = (tot.sum(), tot.size * longest * block,
                  tot.size * int(n_run) * block,
                  tot.size * max_blocks * block)
    return out


@dataclasses.dataclass
class Dispatched:
    """A request :meth:`QueryEngine.dispatch` started and
    :meth:`QueryEngine.collect` finishes: its answers (``ids``, ``dists``,
    padded, on the device unless the route had nothing to run), the pruned
    route's ``scans`` (:func:`_scan_rows`), and the route span, still open
    (``None`` for an empty batch)."""

    Q: int
    route: str
    requested: str
    est: Optional[np.ndarray]
    hits: int
    misses: int
    slots: List[iv.PlanSlot]
    ids: object
    dists: object
    scans: Optional[List[tuple]]
    span: object
    t0: float


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-lifetime tuning for :class:`QueryEngine`, as one typed value.

    This replaces the constructor-knob sprawl (``use_kernel``, ``route``,
    ``graph_fanout``, ...) that accumulated across PRs 1-5: configs validate
    once, travel as a unit (serving fleets, per-shard engines of a
    :class:`repro.distributed.ShardedDeployment`), and derive variants with
    :meth:`replace`. A ``None`` on ``graph_fanout``/``graph_chunk`` means
    *the engine's backend heuristic decides*; a :class:`SearchRequest` field
    overrides both (request wins over config wins over backend heuristic).

    Parameters
    ----------
    use_kernel : bool
        Route distance evaluation through the Pallas kernels. On a TPU the
        graph route refuses it (its fused step kernel needs the whole vector
        table in VMEM); the flat route's scan kernels run.
    route : str
        Default routing policy: ``auto`` | ``graph`` | ``pruned`` | ``flat``.
        A request's ``route`` overrides it per call.
    flat_threshold : float, optional
        ``None`` (default): ``auto`` routes by a work model — the exact
        pruned scan is chosen while its estimated per-query work
        (``mean_selectivity * n`` candidate distances) stays below
        ``route_work_ratio *`` the beam search's (``ef * S``). Pass a float
        for the legacy rule: pruned whenever mean estimated selectivity is
        at or below that fixed fraction of the corpus.
    route_work_ratio : float
        Work-model scan/beam crossover multiplier (only used when
        ``flat_threshold`` is None).
    selectivity_sample : int
        Corpus sample size for the selectivity estimator (whole corpus when
        smaller, making the estimate exact).
    pad_queries : bool
        Pad batches to power-of-two sizes so jit traces are reused across
        ragged serving batches.
    sel_cache_max : int
        Bound on the selectivity memo (FIFO eviction past it).
    graph_fanout : int, optional
        Frontier vertices the wavefront graph search expands per step when a
        request leaves ``fanout=None``. ``None`` (default) picks per
        backend: ``max(1, min(8, ef // 16))`` on TPU (wide steps amortize
        loop latency), 1 elsewhere (per-step op cost dominates).
    graph_chunk : int | "auto" | None
        Steps per compaction slice of the chunked graph driver; between
        slices converged query rows are repacked out of the active batch
        (power-of-two buckets). ``None`` disables chunking (single
        ``lax.while_loop`` to global convergence); ``"auto"`` (default)
        chunks at 16 steps once the padded batch reaches 64 queries — below
        that the per-slice dispatch overhead outweighs the compaction
        savings. Results are bit-identical in every mode. A request's
        ``chunk`` overrides it per call.
    packed_visited : bool
        Use the bit-packed ``(Q, ceil(n/32))`` uint32 visited bitmap (n/8
        bytes per query) instead of the dense ``(Q, n)`` bool reference
        array. Results are bit-identical; the dense path exists for property
        tests and as a fallback.
    trace_sample : float
        Fraction of requests to trace without the caller asking (0.0, the
        default, traces only ``SearchRequest(trace=True)``). Sampling is
        deterministic — every ``round(1/trace_sample)``-th request — so a
        serving process gets a steady trickle of traces on
        ``SearchResult.trace`` rather than a random burst.
    storage_dtype : str, optional
        Vector storage tier the engine *scans*: ``"float32"`` (exact, the
        default), ``"float16"``, or ``"int8"`` (per-dimension affine codes,
        4 bytes/dim -> 1). ``None`` inherits the index's own tier
        (``IndexSpec.storage_dtype``); an explicit value overrides it,
        re-quantizing on the fly when the index was built at a different
        tier. Compressed tiers scan approximate distances over the code
        table and then re-rank the top ``rerank_k`` candidates against the
        exact float32 rows, so end recall is preserved (see README "Vector
        compression"). With a compressed tier the float32 corpus is never
        staged on device — it stays host-side for the re-rank gather.
    rerank_k : int, optional
        How many approximate candidates per query survive to the exact
        float32 re-rank when the storage tier is compressed. ``None``
        (default) uses ``max(4 * k, 32)``; always clamped to
        ``[k, corpus size]`` (and to ``ef`` on the graph route, which can
        never rank more than its pool). Larger values close the recall gap
        at the cost of a wider re-rank gather.
    """

    use_kernel: bool = False
    route: str = ROUTE_AUTO
    flat_threshold: Optional[float] = None
    route_work_ratio: float = 1.0
    selectivity_sample: int = 2048
    pad_queries: bool = True
    sel_cache_max: int = 65536
    graph_fanout: Optional[int] = None
    graph_chunk: Union[int, str, None] = "auto"
    packed_visited: bool = True
    trace_sample: float = 0.0
    storage_dtype: Optional[str] = None
    rerank_k: Optional[int] = None

    def __post_init__(self):
        if self.route not in _ROUTES:
            raise ValueError(f"route must be one of {_ROUTES}, got "
                             f"{self.route!r}")
        if self.graph_fanout is not None and self.graph_fanout < 1:
            raise ValueError("graph_fanout must be >= 1 (or None: backend "
                             f"heuristic), got {self.graph_fanout!r}")
        if not (self.graph_chunk is None or self.graph_chunk == "auto"
                or (isinstance(self.graph_chunk, int)
                    and self.graph_chunk >= 0)):
            raise ValueError("graph_chunk must be an int >= 1, 0/None "
                             "(single-loop driver), or \"auto\", got "
                             f"{self.graph_chunk!r}")
        if self.selectivity_sample < 1:
            raise ValueError("selectivity_sample must be >= 1")
        if self.sel_cache_max < 1:
            raise ValueError("sel_cache_max must be >= 1")
        if not (0.0 <= self.trace_sample <= 1.0):
            raise ValueError("trace_sample must be in [0, 1], got "
                             f"{self.trace_sample!r}")
        if self.storage_dtype is not None:
            check_storage_dtype(self.storage_dtype)
        if self.rerank_k is not None and self.rerank_k < 1:
            raise ValueError("rerank_k must be >= 1 (or None: max(4k, 32)), "
                             f"got {self.rerank_k!r}")

    def replace(self, **overrides) -> "EngineConfig":
        """A copy with ``overrides`` applied (re-validated)."""
        return dataclasses.replace(self, **overrides)


_ENGINE_KNOBS = frozenset(f.name for f in dataclasses.fields(EngineConfig))


class QueryEngine:
    """Unified search facade: plan once, execute on the best engine.

    Parameters
    ----------
    index : MSTGIndex
        Built index; whichever variants it has bound the masks it can serve.
    config : EngineConfig, optional
        Engine-lifetime tuning (kernels, routing policy, wavefront knobs,
        padding, selectivity estimator) — see :class:`EngineConfig` for every
        field. Defaults to ``EngineConfig()``.
    **legacy_knobs
        The pre-config constructor surface (``QueryEngine(index,
        use_kernel=True, graph_chunk=16, ...)``). Deprecated: warns once per
        process and folds the knobs into ``config`` (knobs win over an
        explicitly passed config). New code should construct an
        :class:`EngineConfig`.
    """

    def __init__(self, index: MSTGIndex,
                 config: Optional[EngineConfig] = None, **legacy_knobs):
        if legacy_knobs:
            unknown = sorted(set(legacy_knobs) - _ENGINE_KNOBS)
            if unknown:
                raise TypeError(f"unknown QueryEngine knob(s) {unknown}; "
                                f"valid knobs: {sorted(_ENGINE_KNOBS)}")
            _warn_deprecated(
                "QueryEngine.knobs",
                "bare QueryEngine constructor knobs are deprecated; pass "
                "QueryEngine(index, config=EngineConfig(...))",
                stacklevel=2)
            config = (config or EngineConfig()).replace(**legacy_knobs)
        config = config if config is not None else EngineConfig()
        if not isinstance(config, EngineConfig):
            raise TypeError("config must be an EngineConfig, got "
                            f"{type(config).__name__}")
        self.config = config
        self.index = index
        self.use_kernel = config.use_kernel
        self.default_route = config.route
        self.flat_threshold = (None if config.flat_threshold is None
                               else float(config.flat_threshold))
        self.route_work_ratio = float(config.route_work_ratio)
        self._max_slots = max((fv.nbr.shape[2]
                               for fv in index.variants.values()), default=16)
        self.pad_queries = config.pad_queries
        self.graph_fanout = config.graph_fanout
        self.graph_chunk = config.graph_chunk
        self.packed_visited = bool(config.packed_visited)

        # storage tier: explicit config value wins over the index's own tier.
        # The float32 corpus device copy is lazy (``self.corpus`` property):
        # compressed configurations scan the code table and keep the exact
        # rows host-side for the re-rank gather, so they never stage it.
        sd = check_storage_dtype(config.storage_dtype
                                 or getattr(index.spec, "storage_dtype",
                                            "float32"))
        self.storage_dtype = sd
        store = getattr(index, "storage", None)
        if sd == "float32":
            store = None
        elif store is None or store.dtype != sd:
            store = QuantizedStore.from_vectors(index.vectors, sd)
        self._store: Optional[QuantizedStore] = store
        self._store_dev: Optional[dict] = None
        # router work model: scanning 1-byte codes streams 1/4 the bytes of
        # a float32 scan, so scan work is weighed by the tier's itemsize
        self._scan_cost_ratio = (store.itemsize / 4.0) if store else 1.0
        self._corpus_dev = None
        self.lo = jnp.asarray(index.lo, jnp.float32)
        self.hi = jnp.asarray(index.hi, jnp.float32)
        # per-route device staging is lazy (first use) so graph-only callers
        # never upload pruned member arrays and vice versa
        self._graph_dev: Dict[str, DeviceVariant] = {}
        self._pruned_dev: Dict[str, dict] = {}
        self._sorted_rank: Dict[str, np.ndarray] = {}

        n = index.vectors.shape[0]
        m = min(n, int(config.selectivity_sample))
        sel = (np.arange(n) if m == n
               else np.random.default_rng(0).choice(n, size=m, replace=False))
        self._sample_lo = np.asarray(index.lo)[sel]
        self._sample_hi = np.asarray(index.hi)[sel]
        # O(1)-per-query exact selectivity over the sample via a 2-D rank
        # prefix table — consulted before any device work, so the auto
        # router's cold path costs microseconds, not a sample scan. Falls
        # back to the eval_predicate scan for very large domains.
        dom = index.domain
        self._sel_index: Optional[iv.SelectivityIndex] = None
        if dom.K <= 2048:
            self._sel_index = iv.SelectivityIndex(
                dom.rank(self._sample_lo), dom.rank(self._sample_hi), dom.K)
        self.route_counts: Dict[str, int] = {ROUTE_GRAPH: 0, ROUTE_PRUNED: 0,
                                             ROUTE_FLAT: 0}
        # selectivity memo: (mask, fl, cl, fr, cr) -> sample fraction. The
        # rank signature determines the sample predicate exactly (sample
        # endpoints are domain values), so this is quantization, not change.
        # Bounded FIFO: overflow evicts the oldest entries (dict preserves
        # insertion order), never the whole memo.
        self._sel_cache: Dict[tuple, float] = {}
        self._sel_cache_max = int(config.sel_cache_max)
        self.sel_cache_hits = 0
        self.sel_cache_misses = 0
        self.sel_cache_evictions = 0

        # deterministic trace sampling: every round(1/trace_sample)-th request
        ts = float(config.trace_sample)
        self._trace_every = int(round(1.0 / ts)) if ts > 0 else 0
        self._trace_seq = 0
        # labeled metric children resolved once here so the per-request cost
        # is attribute updates, not name/label lookups
        reg = obs.get_registry()
        req_c = reg.counter("engine_requests_total",
                            "Batch requests executed, by resolved route",
                            labels=("route",))
        qry_c = reg.counter("engine_queries_total",
                            "Individual queries executed, by resolved route",
                            labels=("route",))
        lat_h = reg.histogram("engine_search_ms",
                              "QueryEngine.execute wall time (ms), by route",
                              labels=("route",))
        self._route_metrics = {
            r: (req_c.labels(route=r), qry_c.labels(route=r),
                lat_h.labels(route=r))
            for r in (ROUTE_GRAPH, ROUTE_PRUNED, ROUTE_FLAT)}
        sel_c = reg.counter("engine_sel_cache_total",
                            "Selectivity-memo lookups, by outcome",
                            labels=("outcome",))
        self._m_sel_hit = sel_c.labels(outcome="hit")
        self._m_sel_miss = sel_c.labels(outcome="miss")
        rows_c = reg.counter("engine_pruned_rows_total",
                             "Candidate rows of the pruned route's slot "
                             "scans: needed by the queries, to_longest (a "
                             "scan stopped at the batch's longest prefix), "
                             "scanned (what the scan loop ran), bound (the "
                             "loop's cap, max_blocks)",
                             labels=("kind",))
        self._m_rows = tuple(rows_c.labels(kind=kind) for kind in _ROW_KINDS)

    # ---- device staging (lazy, cached per variant) ----
    @property
    def corpus(self) -> jnp.ndarray:
        """Device-staged float32 corpus, uploaded on first use. Compressed
        storage tiers never touch it — the exact rows stay host-side and are
        gathered per-batch for the re-rank."""
        if self._corpus_dev is None:
            self._corpus_dev = jnp.asarray(self.index.vectors, jnp.float32)
        return self._corpus_dev

    def store_dev(self) -> dict:
        """Device-staged quantized store (codes + affine params), lazy.
        ``codes`` is the row-major (n, d) table the gather paths read;
        ``codes_t`` is the contiguous (d, n) panel layout the blocked
        compressed scan consumes (see :func:`compressed_flat_topr`)."""
        if self._store_dev is None:
            st = self._store
            self._store_dev = dict(
                codes=jnp.asarray(st.codes),
                codes_t=jnp.asarray(np.ascontiguousarray(st.codes.T)),
                scale=jnp.asarray(st.scale),
                offset=jnp.asarray(st.offset),
                sq_norm=jnp.asarray(st.sq_norm))
        return self._store_dev

    def graph_dev(self, variant: str) -> DeviceVariant:
        if self.use_kernel and _backend() == "tpu":
            # the fused wavefront step (kernels/gathered_topk.py) takes the
            # whole (n, d) table as one VMEM block; a real corpus cannot fit
            raise NotImplementedError(
                "use_kernel=True cannot serve the graph route on a TPU: the "
                "gathered_topk kernel holds the whole vector table in VMEM. "
                "Serve graph traffic from an engine with use_kernel=False.")
        if variant not in self._graph_dev:
            fv = self.index.variants[variant]
            self._graph_dev[variant] = (
                DeviceVariant(fv, None, store=self._store)
                if self._store is not None else DeviceVariant(fv, self.corpus))
        return self._graph_dev[variant]

    def pruned_dev(self, variant: str) -> dict:
        if variant not in self._pruned_dev:
            fv = self.index.variants[variant]
            dev = dict(members=jnp.asarray(fv.members),
                       member_ver=jnp.asarray(fv.member_ver),
                       node_off=jnp.asarray(fv.node_off))
            if self._store is not None:
                sd = self.store_dev()
                dev.update(codes=sd["codes"], code_scale=sd["scale"],
                           code_offset=sd["offset"],
                           code_sq_norm=sd["sq_norm"])
            else:
                dev["vectors"] = self.corpus
            self._pruned_dev[variant] = dev
        return self._pruned_dev[variant]

    def _sorted_sort_rank(self, variant: str) -> np.ndarray:
        if variant not in self._sorted_rank:
            self._sorted_rank[variant] = np.sort(
                self.index.variants[variant].sort_rank)
        return self._sorted_rank[variant]

    # ---- planning / routing ----
    def plan(self, mask: int, qlo: np.ndarray, qhi: np.ndarray) -> List[iv.PlanSlot]:
        return self.index.plan_batch(as_mask(mask), qlo, qhi)

    def estimate_selectivity(self, mask, qlo, qhi) -> np.ndarray:
        """(Q,) estimated fraction of the corpus each query's predicate keeps
        (exact when the sample covers the corpus)."""
        return self._estimate_cached(as_mask(mask), qlo, qhi)[0]

    def _estimate_cached(self, mask: int, qlo, qhi) -> Tuple[np.ndarray, int, int]:
        """Memoized selectivity estimate -> (est (Q,), hits, misses).

        Queries are keyed by their exact rank signature (floor/ceil ranks of
        both endpoints): two float ranges with the same signature select the
        same sample objects, so repeated serving traffic is answered from the
        dict instead of re-evaluating the sample predicate."""
        ql = np.asarray(qlo, np.float64)
        qh = np.asarray(qhi, np.float64)
        dom = self.index.domain
        fl, cl = dom.floor_rank(ql), dom.ceil_rank(ql)
        fr, cr = dom.floor_rank(qh), dom.ceil_rank(qh)
        Q = ql.shape[0]
        out = np.empty(Q, np.float64)
        miss: List[int] = []
        hits = 0
        for i in range(Q):
            v = self._sel_cache.get((mask, fl[i], cl[i], fr[i], cr[i]))
            if v is None:
                miss.append(i)
            else:
                out[i] = v
                hits += 1
        if miss:
            mi = np.asarray(miss)
            if self._sel_index is not None:
                est = self._sel_index.fraction(mask, fl[mi], cl[mi],
                                               fr[mi], cr[mi])
            else:
                hit = iv.eval_predicate(mask, self._sample_lo[None, :],
                                        self._sample_hi[None, :],
                                        ql[mi][:, None], qh[mi][:, None])
                est = np.asarray(hit, np.float64).mean(axis=1)
            for j, i in enumerate(miss):
                v = float(est[j])
                self._sel_cache[(mask, fl[i], cl[i], fr[i], cr[i])] = v
                out[i] = v
            overflow = len(self._sel_cache) - self._sel_cache_max
            if overflow > 0:  # FIFO: drop the oldest entries only
                for key in list(itertools.islice(iter(self._sel_cache),
                                                 overflow)):
                    del self._sel_cache[key]
                self.sel_cache_evictions += overflow
        self.sel_cache_hits += hits
        self.sel_cache_misses += len(miss)
        if hits:
            self._m_sel_hit.inc(hits)
        if miss:
            self._m_sel_miss.inc(len(miss))
        return out, hits, len(miss)

    def _auto_route(self, est: np.ndarray, ef: int = 64) -> str:
        """The one auto-routing rule shared by route_for() and execute().

        With an explicit ``flat_threshold`` this is the legacy fixed-fraction
        rule. The default is a *work model*: the pruned scan evaluates
        ~``est * n`` candidate distances per query while the beam search
        evaluates ~``ef * S`` (S = adjacency slots), so route to the exact
        scan whenever its estimated work is below ``route_work_ratio`` times
        the beam's — at small corpora the scan wins far beyond any fixed 5%
        selectivity cutoff, and at millions of rows the crossover drops to
        fractions of a percent, exactly as it should. Scan work is weighed
        by the storage tier's bytes-per-component (int8 codes stream 1/4 the
        bytes of float32, so the bandwidth-bound scan stays competitive to
        4x the selectivity); the beam gathers the same tier either way."""
        if self.flat_threshold is not None:
            return (ROUTE_PRUNED if float(est.mean()) <= self.flat_threshold
                    else ROUTE_GRAPH)
        scan_work = (float(est.mean()) * self.index.vectors.shape[0]
                     * self._scan_cost_ratio)
        beam_work = float(ef) * self._max_slots
        return (ROUTE_PRUNED if scan_work <= self.route_work_ratio * beam_work
                else ROUTE_GRAPH)

    def route_for(self, mask, qlo, qhi, route: Optional[str] = None,
                  ef: int = 64) -> str:
        """Advisory routing answer. Pass the request's actual ``ef`` — the
        work model weighs beam work by it, so the default (64, matching
        ``SearchRequest``'s default) only mirrors ``execute()`` for requests
        that keep that default."""
        route = route or self.default_route
        if route != ROUTE_AUTO:
            return route
        return self._auto_route(self.estimate_selectivity(mask, qlo, qhi), ef)

    # ---- execution ----
    def search(self, request: SearchRequest, **opts) -> SearchResult:
        """Execute a :class:`repro.core.api.SearchRequest` ->
        :class:`repro.core.api.SearchResult`.

        The tuple-era positional form ``search(queries, qlo, qhi, mask, ...)``
        (deprecated since PR 2) was removed in PR 6 — build a
        ``SearchRequest`` instead (README has the migration table).
        """
        if not isinstance(request, SearchRequest):
            raise TypeError(
                "QueryEngine.search takes a repro.core.SearchRequest; the "
                "tuple-era positional form search(queries, qlo, qhi, mask) "
                "was removed — see the README migration guide")
        if opts:
            raise TypeError(
                f"unexpected search option(s) {sorted(opts)} — per-request "
                "knobs (k, ef, route, ...) go on the SearchRequest")
        return self.execute(request)

    def execute(self, request: SearchRequest) -> SearchResult:
        """Plan, route, and run one request; always returns a SearchResult.
        :meth:`dispatch` then :meth:`collect`, in a row.

        ``request.trace=True`` (or a hit of ``EngineConfig.trace_sample``)
        records the request's span tree — plan, route decision, per-slot
        execution — onto ``SearchResult.trace``. When this engine runs as a
        shard of a :class:`repro.distributed.ShardedDeployment`, its spans
        join the deployment's trace instead (inner layers never finish an
        outer trace)."""
        wants_trace = request.trace
        if not wants_trace and self._trace_every:
            self._trace_seq += 1
            wants_trace = (self._trace_seq % self._trace_every) == 0
        tracer = obs.begin_request_trace() if wants_trace else None
        try:
            with obs.span("search") as root:
                root.set("Q", len(request)).set("k", request.k)
                root.set("mask", request.mask)
                p = self.dispatch(request)
                root.set("requested", p.requested)
                result = self.collect(p)
        finally:
            trace = obs.end_request_trace(tracer)
        if trace is not None:
            result = dataclasses.replace(result, trace=trace)
        return result

    def dispatch(self, request: SearchRequest,
                 slots: Optional[List[iv.PlanSlot]] = None) -> Dispatched:
        """Route the request and start its device work, without waiting on
        the device: the answers' copy to the host is under way when this
        returns. ``slots``, when given, is the request's plan (a
        :class:`repro.distributed.ShardedDeployment` plans once for all its
        shards); otherwise the graph and pruned routes plan here. Finish
        with :meth:`collect`, on any device context."""
        requested = request.route or self.default_route
        if requested not in _ROUTES:
            raise ValueError(f"route must be one of {_ROUTES}, "
                             f"got {requested!r}")
        t0 = time.perf_counter()
        queries, qlo, qhi = request.vectors, request.qlo, request.qhi
        mask, k = request.mask, request.k
        Q = len(request)
        est = None
        hits = misses = 0
        route = requested
        if requested == ROUTE_AUTO and Q:
            with obs.span("route") as rsp:
                est, hits, misses = self._estimate_cached(mask, qlo, qhi)
                route = self._auto_route(est, request.ef)
                if obs.tracing():
                    rsp.set("chosen", route)
                    rsp.set("est_mean", round(float(est.mean()), 6))
                    rsp.set("cache_hits", hits).set("cache_misses", misses)
        if route not in (ROUTE_GRAPH, ROUTE_PRUNED):
            slots = []
        if Q == 0:
            ids, d = _empty_result(0, k)
            return Dispatched(0, route, requested, est, hits, misses, [],
                              ids, d, None, None, t0)
        self.route_counts[route] = self.route_counts.get(route, 0) + 1
        if slots is None:
            with obs.span("plan") as psp:
                slots = self.plan(mask, qlo, qhi)
                psp.set("slots", len(slots))
        scans = None
        # the route span stays open until collect() has counted the scans
        rsp = obs.span(route)
        try:
            if route == ROUTE_FLAT:
                ids, d = self._run_flat(queries, qlo, qhi, mask, k)
            elif route == ROUTE_PRUNED:
                ids, d, scans = self._run_pruned(queries, qlo, qhi, mask, k,
                                                 slots=slots)
            elif route == ROUTE_GRAPH:
                ids, d = self._run_graph(queries, qlo, qhi, mask, k,
                                         request.ef, request.max_steps,
                                         request.fanout, slots=slots,
                                         chunk=request.chunk)
            else:
                raise ValueError(f"unknown route {route!r}")
        except BaseException:
            rsp.stop()
            raise
        for a in (ids, d):
            if isinstance(a, jax.Array):
                a.copy_to_host_async()
        return Dispatched(Q, route, requested, est, hits, misses, slots, ids,
                          d, scans, rsp, t0)

    def collect(self, p: Dispatched) -> SearchResult:
        """Wait for the answers of a :meth:`dispatch` and report them."""
        ids, d = p.ids, p.dists
        if p.span is not None:
            # the host waits here until the answers are on it
            with obs.span("fetch"):
                ids, d = np.asarray(ids)[:p.Q], np.asarray(d)[:p.Q]
            if p.scans is not None:
                self._count_scans(p.scans, p.span)
            p.span.stop()
        report = RouteReport(route=p.route, requested=p.requested,
                             est_selectivity=p.est,
                             slot_count=len(p.slots),
                             variants=tuple(s.variant for s in p.slots),
                             cache_hits=p.hits, cache_misses=p.misses)
        rm = self._route_metrics.get(p.route)
        if rm is not None:
            rm[0].inc()
            rm[1].inc(float(p.Q))
            rm[2].record((time.perf_counter() - p.t0) * 1e3)
        return SearchResult(ids, d, report)

    # Convenience fixed-route entry points (legacy tuple returns).
    def search_graph(self, queries, qlo, qhi, mask, k=10, ef=64,
                     max_steps=None, fanout=1):
        req = SearchRequest(queries, (qlo, qhi), mask, k=k, ef=ef,
                            max_steps=max_steps, fanout=fanout,
                            route=ROUTE_GRAPH)
        return self.execute(req).astuple()

    def search_pruned(self, queries, qlo, qhi, mask, k=10, block: int = 256,
                      max_candidates: Optional[int] = None):
        queries = np.ascontiguousarray(queries, np.float32)
        qlo = np.asarray(qlo, np.float64)
        qhi = np.asarray(qhi, np.float64)
        mask = as_mask(mask)
        Q = queries.shape[0]
        if Q == 0:
            return _empty_result(0, k)
        self.route_counts[ROUTE_PRUNED] = self.route_counts.get(ROUTE_PRUNED, 0) + 1
        with obs.span(ROUTE_PRUNED) as rsp:
            ids, d, scans = self._run_pruned(queries, qlo, qhi, mask, k,
                                             block=block,
                                             max_candidates=max_candidates)
            with obs.span("fetch"):
                ids, d = np.asarray(ids[:Q]), np.asarray(d[:Q])
            self._count_scans(scans, rsp)
        return ids, d

    def search_flat(self, queries, qlo, qhi, mask, k=10):
        req = SearchRequest(queries, (qlo, qhi), mask, k=k, route=ROUTE_FLAT)
        return self.execute(req).astuple()

    # ---- internals ----
    def _padded(self, queries: np.ndarray, qlo: np.ndarray, qhi: np.ndarray):
        """Pad the batch to a power-of-two bucket; padded rows use the
        impossible query range [0, -1] so no predicate bit can select them."""
        Q = queries.shape[0]
        if not self.pad_queries:
            return queries, qlo, qhi
        Qp = max(_next_pow2(Q), 8)
        if Qp == Q:
            return queries, qlo, qhi
        pad = Qp - Q
        queries = np.concatenate(
            [queries, np.zeros((pad, queries.shape[1]), np.float32)])
        qlo = np.concatenate([qlo, np.zeros(pad)])
        qhi = np.concatenate([qhi, np.full(pad, -1.0)])
        return queries, qlo, qhi

    def _padded_slots(self, slots: List[iv.PlanSlot], Qp: int) -> List[iv.PlanSlot]:
        """Extend each slot's per-query arrays with empty tasks (version=-1,
        key_lo>key_hi): padded queries start with an empty pool and terminate
        on the first loop-condition check."""
        out = []
        for s in slots:
            pad = Qp - s.version.shape[0]
            if pad <= 0:
                out.append(s)
                continue
            out.append(iv.PlanSlot(
                s.variant,
                np.concatenate([s.version, np.full(pad, -1, np.int64)]),
                np.concatenate([s.key_lo, np.ones(pad, np.int64)]),
                np.concatenate([s.key_hi, np.zeros(pad, np.int64)])))
        return out

    def _resolve_fanout(self, ef: int, fanout: Optional[int]) -> int:
        """Wavefront width: an explicit request value wins, then the engine
        override, then a backend heuristic — on TPU wide steps amortize loop
        latency over fanout x S distance evals (total expansions stay ~ef
        either way); on CPU the per-step op cost grows with the width, so
        the narrow frontier is the fast one."""
        if fanout:
            return max(1, int(fanout))
        if self.graph_fanout:
            return max(1, int(self.graph_fanout))
        if _backend() == "tpu":
            return max(1, min(8, ef // 16))
        return 1

    def _count_scans(self, scans: List[tuple], sp) -> None:
        """Add the pruned route's candidate rows (:func:`_scan_rows`) to
        ``engine_pruned_rows_total`` and, while tracing, to the route span
        ``sp``. Called after the answers' fetch, so it adds no wait."""
        rows = _scan_rows(scans).sum(axis=0)
        for child, v in zip(self._m_rows, rows):
            child.inc(float(v))
        if obs.tracing():
            needed, to_longest, scanned, bound = (int(v) for v in rows)
            sp.set("slots", len(scans)).set("rows_needed", needed)
            sp.set("rows_scanned", scanned).set("rows_to_longest", to_longest)
            sp.set("rows_bound", bound)

    def _rerank_width(self, k: int, upper: Optional[int] = None) -> int:
        """Approximate candidates per query surviving to the exact re-rank:
        ``rerank_k`` (default ``max(4k, 32)``) clamped to [k, n] and to
        ``upper`` (the graph pool width ``ef``) when given."""
        n = self.index.vectors.shape[0]
        R = self.config.rerank_k or max(4 * k, 32)
        if upper is not None:
            R = min(R, upper)
        return max(k, min(R, n))

    def _rerank_exact(self, qdev, cand_ids, k: int):
        """Exact float32 re-rank of approximate top-R candidate ids: gather
        the exact rows host-side (the f32 corpus is never device-staged on a
        compressed tier) and re-rank on device."""
        cand = np.asarray(cand_ids)
        rows = self.index.vectors[np.clip(cand, 0, None)]
        with obs.span("rerank") as rsp:
            if obs.tracing():
                rsp.set("R", int(cand.shape[1]))
            return exact_rerank(qdev, jnp.asarray(rows), jnp.asarray(cand),
                                k=k)

    def _run_graph(self, queries, qlo, qhi, mask, k, ef, max_steps, fanout,
                   slots: Optional[List[iv.PlanSlot]] = None,
                   chunk: Optional[int] = None):
        if slots is None:
            slots = self.plan(mask, qlo, qhi)
        F = self._resolve_fanout(ef, fanout)
        chunk = chunk if chunk is not None else self.graph_chunk
        queries_p, _, _ = self._padded(queries, qlo, qhi)
        if chunk == "auto":  # compaction pays once the batch is wide enough
            chunk = 16 if queries_p.shape[0] >= 64 else None
        slots = self._padded_slots(slots, queries_p.shape[0])
        steps = max_steps or ((4 * ef + 64) // F + 8)
        qdev = jnp.asarray(queries_p)
        # compressed tier: the beam ranks approximate (dequantized-gather)
        # distances, so carry top-R of the pool through the merge and
        # re-rank exactly at the end. R can't exceed the pool width ef.
        kq = k if self._store is None else self._rerank_width(k, upper=ef)
        res = None
        for s in slots:
            # skip slots where every query's task is empty before any device
            # work (empty tasks produce all-NO_EDGE rows; merging them is a
            # no-op, so skipping is result-identical)
            if not np.any((s.version >= 0) & (s.key_lo <= s.key_hi)):
                continue
            dv = self.graph_dev(s.variant)
            common = dict(k=kq, ef=ef, max_steps=steps, Kpad=dv.meta.Kpad,
                          use_kernel=self.use_kernel, fanout=F,
                          packed=self.packed_visited)
            with obs.span("slot") as ssp:
                ssp.set("variant", s.variant).set("ef", ef).set("fanout", F)
                if chunk and chunk < steps:
                    ssp.set("chunk", int(chunk))
                    ids, d = mstg_graph_search_chunked(
                        dv.tree(), qdev, s.version, s.key_lo, s.key_hi,
                        chunk=int(chunk), **common)
                else:
                    ids, d = mstg_graph_search(
                        dv.tree(), qdev, jnp.asarray(s.version, jnp.int32),
                        jnp.asarray(s.key_lo, jnp.int32),
                        jnp.asarray(s.key_hi, jnp.int32), **common)
            res = (ids, d) if res is None else merge_topk(res[0], res[1], ids,
                                                          d, kq)
        if res is None:
            return _empty_result(queries_p.shape[0], k)
        if self._store is not None:
            return self._rerank_exact(qdev, res[0], k)
        return res

    def _run_pruned(self, queries, qlo, qhi, mask, k, block: int = 256,
                    max_candidates: Optional[int] = None,
                    slots: Optional[List[iv.PlanSlot]] = None):
        """Dispatch one scan per non-empty plan slot and merge them. Returns
        ``(ids, dists, scans)`` with the answers still on the device and
        ``scans`` as :func:`_scan_rows` reads it; nothing here waits on the
        device (bar the compressed tier's re-rank)."""
        if slots is None:
            slots = self.plan(mask, qlo, qhi)
        n = self.index.vectors.shape[0]
        queries_p, qlo_p, qhi_p = self._padded(queries, qlo, qhi)
        Qp = queries_p.shape[0]
        slots = self._padded_slots(slots, Qp)
        qdev = jnp.asarray(queries_p)
        qlo_j = jnp.asarray(qlo_p, jnp.float32)
        qhi_j = jnp.asarray(qhi_p, jnp.float32)
        # compressed tier: scan distances are approximate, so keep top-R per
        # slot and through the merge, then re-rank exactly once at the end
        kq = k if self._store is None else self._rerank_width(k)
        res = None
        scans = []
        for s in slots:
            fv = self.index.variants[s.variant]
            # exact candidate upper bound for this slot: objects with
            # sort_rank <= max version (key-range pruning only shrinks it),
            # rounded to a power of two so max_blocks hits the jit cache —
            # never truncates, so the pruned route stays recall-1.0. It only
            # caps the scan loop, which stops on the device at the batch's
            # longest candidate prefix, so the host never waits to size it
            if max_candidates is not None:
                cap = min(n, int(max_candidates))
            else:
                hi_ver = int(s.version.max(initial=-1))
                cap = int(np.searchsorted(self._sorted_sort_rank(s.variant),
                                          hi_ver, side="right"))
                cap = min(n, _next_pow2(cap)) if cap else 0
            if cap == 0:
                continue  # every query's task in this slot is empty
            max_blocks = -(-cap // block)
            # times the host's dispatch of the scan; its device time is the
            # profiler's to measure
            with obs.span("slot") as ssp:
                ssp.set("variant", s.variant).set("candidates", cap)
                ssp.set("rows", Qp).set("max_blocks", max_blocks)
                ssp.set("block", block)
                ids, d, total, n_run = _pruned_search_variant(
                    self.pruned_dev(s.variant), self.lo, self.hi, qdev,
                    qlo_j, qhi_j, jnp.asarray(s.version, jnp.int32),
                    jnp.asarray(s.key_lo, jnp.int32), jnp.asarray(s.key_hi, jnp.int32),
                    pred_mask_bits=mask, k=kq, Kpad=fv.Kpad, block=block,
                    max_blocks=max_blocks)
            # start the totals' copy to the host now, so that it is there by
            # the time the answers are: fetching them then costs no wait
            total.copy_to_host_async()
            n_run.copy_to_host_async()
            scans.append((total, n_run, max_blocks, block))
            if res is None:
                res = (ids, d)
            else:
                with obs.span("merge"):     # times the dispatch, as "slot"
                    res = merge_topk(res[0], res[1], ids, d, kq)
        if res is None:
            return (*_empty_result(Qp, k), scans)
        if self._store is not None:
            return (*self._rerank_exact(qdev, res[0], k), scans)
        return (*res, scans)

    def _run_flat(self, queries, qlo, qhi, mask, k):
        queries_p, qlo_p, qhi_p = self._padded(queries, qlo, qhi)
        qdev = jnp.asarray(queries_p)
        qlo_j = jnp.asarray(qlo_p, jnp.float32)
        qhi_j = jnp.asarray(qhi_p, jnp.float32)
        if self._store is None:
            return flat_search(self.corpus, self.lo, self.hi, qdev,
                               qlo_j, qhi_j,
                               mask=mask, k=k, use_kernel=self.use_kernel)
        sd = self.store_dev()
        R = self._rerank_width(k)
        if self.use_kernel:
            from repro.kernels import ops as kops
            if self._store.dtype == "int8":
                approx = kops.pairwise_l2_int8(
                    qdev, sd["codes"], sd["scale"], sd["offset"],
                    sd["sq_norm"], self.lo, self.hi, qlo_j, qhi_j, mask)
            else:
                # float16 codes are affine-trivial (scale 1, offset 0): the
                # float32 kernel's in-VMEM upcast of the streamed tile is
                # exactly the dequantization
                approx = kops.pairwise_l2_masked(qdev, sd["codes"], self.lo,
                                                 self.hi, qlo_j, qhi_j, mask)
            cand_ids, _ = topr_from_dists(approx, rerank=R)
        else:
            cand_ids, _ = compressed_flat_topr(
                sd["codes_t"], sd["scale"], sd["offset"], sd["sq_norm"],
                self.lo, self.hi, qdev, qlo_j, qhi_j, mask=mask, rerank=R)
        return self._rerank_exact(qdev, cand_ids, k)
