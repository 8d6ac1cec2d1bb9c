"""Wavefront MSTG graph search in JAX (paper Algorithm 4, generalized §4.1/§4.4).

TPU-native execution of the paper's search: a ``lax.while_loop`` advances a
whole query batch; each step expands the ``fanout`` closest unexpanded pool
vertices per query with

    1. one gather from the per-level labeled adjacency (the decomposition nodes
       are disjoint, so a vertex's neighbors live at exactly one level),
    2. label masking  b <= version <= e  (this IS the paper's "never traverse a
       non-qualifying vertex" guarantee — edges only connect qualifying members),
    3. a batched distance evaluation (Pallas kernel on TPU, jnp fallback), and
    4. a sorted pool merge (keep the L best).

Termination matches Algorithm 4: a query is done when its L best are all
expanded. Results for two-task plans (Theorem 4.1) are merged with id-dedupe.

Beyond the seed implementation, this module is a *wavefront engine*:

* **bit-packed visited sets** — the per-query visited structure is a
  ``(Q, ceil(n/32))`` uint32 bitmap instead of a dense ``(Q, n)`` bool array
  (8x smaller state, cheaper while-loop carries; ``packed=False`` keeps the
  dense reference path, property-tested bit-identical).
* **chunked execution + active-batch compaction** —
  :func:`mstg_graph_search_chunked` runs the loop in fixed-size step chunks
  and, between chunks, repacks the still-active query rows into a smaller
  power-of-two bucket, so converged queries stop paying gather + distance
  cost while the slowest queries finish. Per-row trajectories are
  independent, so chunked results are bit-identical to the single-loop ones.
* **fused merge kernel** — with ``use_kernel=True`` the per-step gather →
  distance → label-mask → pool-merge chain runs as one Pallas kernel
  (:mod:`repro.kernels.gathered_topk`) instead of a gather + einsum +
  concat + ``top_k(L + F*S)`` op chain.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs

from . import segment_tree as st
from .hnsw import NO_EDGE
from .mstg import FrozenVariant

INF = jnp.inf


class DeviceVariant:
    """FrozenVariant arrays staged on device.

    With ``store`` (a :class:`repro.core.quant.QuantizedStore`) the staged
    vector table is the int8/float16 *code* matrix plus its (d,) affine
    dequant params — the float32 corpus never reaches the device; the
    wavefront dequantizes gathered candidate rows on the fly and the engine
    re-ranks the final beam against the host-side float32 rows."""

    def __init__(self, fv: FrozenVariant, vectors: np.ndarray, store=None):
        self.meta = fv
        if store is not None:
            self.vectors = jnp.asarray(store.codes)
            self.vec_scale = jnp.asarray(store.scale, jnp.float32)
            self.vec_offset = jnp.asarray(store.offset, jnp.float32)
        else:
            self.vectors = jnp.asarray(vectors, jnp.float32)
            self.vec_scale = None
            self.vec_offset = None
        self.sort_rank = jnp.asarray(fv.sort_rank)
        self.tkey = jnp.asarray(fv.tkey)
        self.nbr = jnp.asarray(fv.nbr)
        self.lab_b = jnp.asarray(fv.lab_b)
        self.lab_e = jnp.asarray(fv.lab_e)
        self.entry_ids = jnp.asarray(fv.entry_ids)
        self.entry_ver = jnp.asarray(fv.entry_ver)
        self.members = jnp.asarray(fv.members)
        self.member_ver = jnp.asarray(fv.member_ver)
        self.node_off = jnp.asarray(fv.node_off)

    def tree(self):
        t = dict(vectors=self.vectors, sort_rank=self.sort_rank,
                 tkey=self.tkey, nbr=self.nbr, lab_b=self.lab_b,
                 lab_e=self.lab_e, entry_ids=self.entry_ids,
                 entry_ver=self.entry_ver, members=self.members,
                 member_ver=self.member_ver, node_off=self.node_off)
        # quant keys only exist on quantized layouts: their presence is
        # static per jit trace, so float32 programs are unchanged
        if self.vec_scale is not None:
            t["vec_scale"] = self.vec_scale
            t["vec_offset"] = self.vec_offset
        return t


def _tree_quant(arrays: dict):
    """(scale, offset) when ``arrays`` is a quantized layout, else None.
    Dict-key presence is resolved at trace time."""
    if "vec_scale" in arrays:
        return arrays["vec_scale"], arrays["vec_offset"]
    return None


def _gather_dequant(vectors, idx, quant):
    """Gather rows by index and, on quantized tables, apply the affine
    dequant to the gathered tile only (the full table stays compressed)."""
    cand = vectors[idx]
    if quant is None:
        return cand
    scale, offset = quant
    shape = (1,) * (cand.ndim - 1) + (-1,)
    return (cand.astype(jnp.float32) * scale.reshape(shape)
            + offset.reshape(shape))


def _batched_l2(queries: jnp.ndarray, cand_vecs: jnp.ndarray) -> jnp.ndarray:
    """(Q, d) x (Q, S, d) -> (Q, S) squared L2. jnp fallback; the Pallas path
    is selected in repro.kernels.ops."""
    diff = cand_vecs - queries[:, None, :]
    return jnp.einsum("qsd,qsd->qs", diff, diff,
                      precision=jax.lax.Precision.HIGHEST)


def _dist_fn(use_kernel: bool):
    """The one candidate-distance dispatch shared by every driver (the
    single-shot search, the chunked init, and the chunk runner must stay on
    the same path for their bit-identity contract)."""
    if use_kernel:
        from repro.kernels import ops as kops
        return lambda q, c: kops.gathered_l2(q, c)
    return _batched_l2


# ---- bit-packed visited sets ------------------------------------------------

def packed_words(n: int) -> int:
    """uint32 words per query row of a packed visited bitmap (n/8 bytes)."""
    return (int(n) + 31) // 32


def _visited_init(Q: int, n: int, packed: bool):
    if packed:
        return jnp.zeros((Q, packed_words(n)), jnp.uint32)
    return jnp.zeros((Q, n), bool)


def _visited_get(visited, qix, ids, packed: bool):
    """(Q, M) bool: is each (clamped, >=0) id already visited in its row."""
    if packed:
        w = visited[qix[:, None], ids >> 5]
        return ((w >> (ids & 31).astype(jnp.uint32)) & jnp.uint32(1)) != 0
    return visited[qix[:, None], ids]


def _visited_set(visited, qix, ids, mark, packed: bool):
    """Set the bits for ``ids`` where ``mark``. Marked ids must be unique per
    row and not yet visited (the callers guarantee both), so the packed
    scatter-add touches each bit at most once and equals a scatter-OR."""
    if packed:
        bit = jnp.uint32(1) << (ids & 31).astype(jnp.uint32)
        upd = jnp.where(mark, bit, jnp.uint32(0))
        return visited.at[qix[:, None], ids >> 5].add(upd)
    return visited.at[qix[:, None], ids].max(mark)


def _first_occurrence(ids):
    """(Q, M) bool: True at the first occurrence of each value per row.
    O(M^2) pairwise compare — far cheaper than the sort/inverse-sort
    formulation for the small M = fanout * slots widths of the step loop."""
    eq = ids[:, :, None] == ids[:, None, :]
    earlier = jnp.tril(jnp.ones((ids.shape[1], ids.shape[1]), bool), k=-1)
    return ~jnp.any(eq & earlier[None], axis=2)


# ---- search state construction ----------------------------------------------

def _active_rows(pool_d, expanded):
    """A query is live while any finite pool entry is unexpanded."""
    return jnp.any(~expanded & jnp.isfinite(pool_d), axis=1)


def _plan_nodes(key_lo, key_hi, Kpad: int):
    """Per-query canonical decomposition + covered key ranges (loop-invariant,
    computed once and carried beside the mutable state)."""
    levels, idxs, valid = jax.vmap(
        lambda a, b: st.decompose_jax(a, b, Kpad))(key_lo, key_hi)
    start, end = st.node_ranges_jax(levels, idxs, Kpad)
    return levels, idxs, valid, start, end


def _init_state(vectors, entry_ids, entry_ver, queries, version,
                levels, idxs, valid, *, L: int, dist_fn, packed: bool,
                quant=None):
    """Initial pool from per-node entry points + visited marking."""
    Q = queries.shape[0]
    n = vectors.shape[0]
    ent = entry_ids[levels, idxs]            # (Q, P, E)
    ever = entry_ver[levels, idxs]           # (Q, P, E)
    ent_ok = valid[:, :, None] & (ent != NO_EDGE) & (ever <= version[:, None, None])
    ent = jnp.where(ent_ok, ent, 0).reshape(Q, -1)
    ent_ok = ent_ok.reshape(Q, -1)
    ed = dist_fn(queries, _gather_dequant(vectors, ent, quant))
    ed = jnp.where(ent_ok, ed, INF)
    ent = jnp.where(ent_ok, ent, NO_EDGE)

    order = jnp.argsort(ed, axis=1)
    take = min(L, ent.shape[1])
    pool_ids = jnp.full((Q, L), NO_EDGE, jnp.int32)
    pool_d = jnp.full((Q, L), INF, jnp.float32)
    pool_ids = pool_ids.at[:, :take].set(
        jnp.take_along_axis(ent, order, 1)[:, :take].astype(jnp.int32))
    pool_d = pool_d.at[:, :take].set(jnp.take_along_axis(ed, order, 1)[:, :take])
    expanded = jnp.zeros((Q, L), bool)

    qix = jnp.arange(Q)
    mark = ent != NO_EDGE
    ent_safe = jnp.where(mark, ent, 0)
    if packed:
        # entries across disjoint decomposition nodes are distinct vertices;
        # the dedupe is defensive (a duplicate would double-add its bit)
        sentinel = jnp.where(mark, ent, n + jnp.arange(ent.shape[1])[None, :])
        mark = mark & _first_occurrence(sentinel)
    visited = _visited_init(Q, n, packed)
    visited = _visited_set(visited, qix, ent_safe, mark, packed)
    alive_steps = jnp.zeros((Q,), jnp.int32)
    return pool_ids, pool_d, expanded, visited, alive_steps


def _make_body(vectors, tkey, nbr, lab_b, lab_e, queries, version,
               levels, idxs, valid, start, end, *, L: int, F: int,
               dist_fn, packed: bool, use_kernel: bool, quant=None):
    """The per-step wavefront body, shared by the single-shot and chunked
    drivers. State: (pool_ids, pool_d, expanded, visited, alive_steps, step)."""
    Q = queries.shape[0]
    S = nbr.shape[2]
    n = vectors.shape[0]
    qix = jnp.arange(Q)

    def body(state):
        pool_ids, pool_d, expanded, visited, alive_steps, step = state
        alive_steps = alive_steps + _active_rows(pool_d, expanded).astype(jnp.int32)
        frontier_d = jnp.where(expanded, INF, pool_d)
        # expand the F closest unexpanded pool vertices at once
        neg_fd, slot = jax.lax.top_k(-frontier_d, F)               # (Q, F)
        act = jnp.isfinite(-neg_fd)
        u = jnp.take_along_axis(pool_ids, slot, 1)                 # (Q, F)
        u_safe = jnp.where(act, u, 0)
        expanded = expanded.at[qix[:, None], slot].max(act)

        # which decomposition node covers u -> its level   (Q, F)
        t = tkey[u_safe][..., None]                                # (Q, F, 1)
        inside = (valid[:, None, :] & (t >= start[:, None, :]) &
                  (t <= end[:, None, :]))                          # (Q, F, P)
        lvl = jnp.max(jnp.where(inside, levels[:, None, :], -1), axis=-1)
        lvl_safe = jnp.clip(lvl, 0, nbr.shape[0] - 1)
        tg = nbr[lvl_safe, u_safe].reshape(Q, F * S)               # (Q, F*S)
        b = lab_b[lvl_safe, u_safe].reshape(Q, F * S)
        e = lab_e[lvl_safe, u_safe].reshape(Q, F * S)
        ok = jnp.repeat(act & (lvl >= 0), S, axis=1) & (tg != NO_EDGE)
        ok &= (b <= version[:, None]) & (version[:, None] <= e)
        tg_safe = jnp.where(ok, tg, 0)
        # dedupe within the step: keep only the first occurrence of each id
        # (one vertex's slot list never repeats a live target, so F == 1
        # needs no dedupe; across fanout rows targets can collide). Invalid
        # slots get out-of-range sentinels so they can never shadow the real
        # corpus vertex 0 (the 0-fill of tg_safe would).
        seen = _visited_get(visited, qix, tg_safe, packed)
        if F > 1:
            sentinel = jnp.where(
                ok, tg, n + jnp.arange(F * S, dtype=jnp.int32)[None, :])
            ok &= _first_occurrence(sentinel)
        new = ok & ~seen
        visited = _visited_set(visited, qix, tg_safe, new, packed)

        if use_kernel:
            from repro.kernels import ops as kops
            if quant is not None:
                pool_ids, pool_d, expanded = kops.gathered_topk_quant(
                    queries, vectors, quant[0], quant[1], tg, new, b, e,
                    version, pool_ids, pool_d, expanded)
            else:
                pool_ids, pool_d, expanded = kops.gathered_topk(
                    queries, vectors, tg, new, b, e, version,
                    pool_ids, pool_d, expanded)
        else:
            nd = dist_fn(queries, _gather_dequant(vectors, tg_safe, quant))
            nd = jnp.where(new, nd, INF)
            cat_ids = jnp.concatenate(
                [pool_ids, jnp.where(new, tg, NO_EDGE)], axis=1)
            cat_d = jnp.concatenate([pool_d, nd], axis=1)
            cat_exp = jnp.concatenate(
                [expanded, jnp.zeros((Q, F * S), bool)], axis=1)
            neg, order = jax.lax.top_k(-cat_d, L)
            pool_ids = jnp.take_along_axis(cat_ids, order, 1)
            pool_d = -neg
            expanded = jnp.take_along_axis(cat_exp, order, 1)
        return pool_ids, pool_d, expanded, visited, alive_steps, step + 1

    return body


# ---- single-shot driver (one jitted call, runs to global convergence) -------

@functools.partial(jax.jit, static_argnames=("k", "ef", "max_steps", "Kpad",
                                              "use_kernel", "fanout",
                                              "with_steps", "packed"))
def mstg_graph_search(arrays: dict, queries: jnp.ndarray, version: jnp.ndarray,
                      key_lo: jnp.ndarray, key_hi: jnp.ndarray, *, k: int,
                      ef: int, max_steps: int, Kpad: int,
                      use_kernel: bool = False, fanout: int = 1,
                      with_steps: bool = False,
                      packed: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched beam search on one MSTG variant.

    arrays   : DeviceVariant.tree()
    queries  : (Q, d) float32
    version  : (Q,) int32 — max valid sort rank (< 0 => empty task)
    key_lo/hi: (Q,) int32 — inclusive tree-key range (lo > hi => empty)
    fanout   : frontier vertices expanded per loop step (beyond-paper: TPU
               amortizes loop latency over fanout x S distance evals; see
               EXPERIMENTS.md §Perf)
    packed   : bit-packed (Q, ceil(n/32)) uint32 visited bitmap (default) vs
               the dense (Q, n) bool reference — bit-identical results
    returns  : ids (Q, k) int32 (NO_EDGE pad), dists (Q, k) float32 (+inf pad)
    """
    vectors = arrays["vectors"]
    quant = _tree_quant(arrays)
    version = version.astype(jnp.int32)
    L = ef
    dist_fn = _dist_fn(use_kernel)
    levels, idxs, valid, start, end = _plan_nodes(key_lo, key_hi, Kpad)
    pool_ids, pool_d, expanded, visited, alive_steps = _init_state(
        vectors, arrays["entry_ids"], arrays["entry_ver"], queries, version,
        levels, idxs, valid, L=L, dist_fn=dist_fn, packed=packed, quant=quant)

    body = _make_body(vectors, arrays["tkey"], arrays["nbr"], arrays["lab_b"],
                      arrays["lab_e"], queries, version, levels, idxs, valid,
                      start, end, L=L, F=fanout, dist_fn=dist_fn,
                      packed=packed, use_kernel=use_kernel, quant=quant)

    def cond(state):
        pool_ids, pool_d, expanded, visited, alive_steps, step = state
        return (step < max_steps) & jnp.any(_active_rows(pool_d, expanded))

    state = (pool_ids, pool_d, expanded, visited, alive_steps,
             jnp.array(0, jnp.int32))
    pool_ids, pool_d, expanded, visited, alive_steps, n_steps = \
        jax.lax.while_loop(cond, body, state)
    if with_steps:
        return pool_ids[:, :k], pool_d[:, :k], n_steps
    return pool_ids[:, :k], pool_d[:, :k]


# ---- chunked driver (wavefront compaction between chunks) -------------------

@functools.partial(jax.jit, static_argnames=("ef", "Kpad", "use_kernel",
                                              "packed"))
def _graph_init(arrays, queries, version, key_lo, key_hi, *, ef, Kpad,
                use_kernel, packed):
    version = version.astype(jnp.int32)
    dist_fn = _dist_fn(use_kernel)
    levels, idxs, valid, start, end = _plan_nodes(key_lo, key_hi, Kpad)
    pool_ids, pool_d, expanded, visited, alive_steps = _init_state(
        arrays["vectors"], arrays["entry_ids"], arrays["entry_ver"], queries,
        version, levels, idxs, valid, L=ef, dist_fn=dist_fn, packed=packed,
        quant=_tree_quant(arrays))
    nodes = (levels, idxs, valid, start, end)
    state = (pool_ids, pool_d, expanded, visited, alive_steps,
             jnp.array(0, jnp.int32))
    return nodes, state, _active_rows(pool_d, expanded)


@functools.partial(jax.jit, static_argnames=("ef", "Kpad", "use_kernel",
                                              "fanout", "packed"))
def _graph_chunk(arrays, queries, version, nodes, state, limit, *, ef, Kpad,
                 use_kernel, fanout, packed):
    """Advance ``state`` by up to ``limit`` (dynamic) steps, returning the new
    state, per-row active flags, and the number of steps actually run."""
    version = version.astype(jnp.int32)
    dist_fn = _dist_fn(use_kernel)
    levels, idxs, valid, start, end = nodes
    body = _make_body(arrays["vectors"], arrays["tkey"], arrays["nbr"],
                      arrays["lab_b"], arrays["lab_e"], queries, version,
                      levels, idxs, valid, start, end, L=ef, F=fanout,
                      dist_fn=dist_fn, packed=packed, use_kernel=use_kernel,
                      quant=_tree_quant(arrays))
    step0 = state[-1]
    bound = step0 + limit.astype(jnp.int32)

    def cond(state):
        pool_ids, pool_d, expanded, visited, alive_steps, step = state
        return (step < bound) & jnp.any(_active_rows(pool_d, expanded))

    state = jax.lax.while_loop(cond, body, state)
    return state, _active_rows(state[1], state[2]), state[-1] - step0


@jax.jit
def _gather_rows(tree, idx):
    """Row-compact a state pytree (retraces per (shape-in, bucket) pair; both
    are power-of-two bounded by the engine's padding policy)."""
    return jax.tree_util.tree_map(lambda a: a if a.ndim == 0 else a[idx], tree)


def _harvest(state, idx: np.ndarray, k: int):
    """Pull converged rows to host. Plain numpy slicing — harvest sets have
    arbitrary sizes, so a jitted version would retrace per size and grow the
    jit cache without bound on a serving path."""
    pool_ids, pool_d, expanded, visited, alive_steps, step = state
    return (np.asarray(pool_ids)[idx, :k], np.asarray(pool_d)[idx, :k],
            np.asarray(alive_steps)[idx])


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def mstg_graph_search_chunked(arrays: dict, queries, version, key_lo, key_hi,
                              *, k: int, ef: int, max_steps: int, Kpad: int,
                              use_kernel: bool = False, fanout: int = 1,
                              chunk: int = 16, min_bucket: int = 8,
                              packed: bool = True, with_stats: bool = False):
    """Wavefront driver: run the beam search in ``chunk``-step slices and
    compact the still-active rows to a power-of-two bucket between slices.

    Per-row trajectories are independent (a converged row's step is the
    identity), so results are bit-identical to :func:`mstg_graph_search` with
    the same parameters — compaction only stops converged queries from paying
    gather + distance cost while stragglers finish.

    Returns ``(ids, dists)`` as numpy arrays, plus a stats dict when
    ``with_stats`` (total steps, per-query convergence steps, executed vs
    useful candidate-evaluation counts).
    """
    queries = jnp.asarray(queries, jnp.float32)
    version = jnp.asarray(version, jnp.int32)
    key_lo = jnp.asarray(key_lo, jnp.int32)
    key_hi = jnp.asarray(key_hi, jnp.int32)
    k = min(k, ef)     # the beam holds ef entries (single-shot slices likewise)
    chunk = max(int(chunk), 1)   # chunk=0 ("single-loop") belongs to the
    Q = queries.shape[0]         # engine; here it would make zero progress
    S = arrays["nbr"].shape[2]
    kw = dict(ef=ef, Kpad=Kpad, use_kernel=use_kernel, packed=packed)

    out_ids = np.full((Q, k), NO_EDGE, np.int32)
    out_d = np.full((Q, k), np.inf, np.float32)
    conv_steps = np.zeros(Q, np.int64)

    nodes, state, active = _graph_init(arrays, queries, version, key_lo,
                                       key_hi, **kw)
    qs, ver = queries, version
    perm = np.arange(Q)                      # current row -> original query
    active_h = np.asarray(active)
    total = 0
    executed_row_steps = 0
    harvested = np.zeros(Q, bool)

    def harvest(rows: np.ndarray) -> None:
        if rows.size == 0:
            return
        ids_h, d_h, steps_h = _harvest(state, rows, k)
        orig = perm[rows]
        out_ids[orig] = ids_h
        out_d[orig] = d_h
        conv_steps[orig] = steps_h
        harvested[orig] = True

    while True:
        live = np.flatnonzero(active_h)
        done = np.flatnonzero(~active_h)
        # harvest rows not yet written (duplicated pad rows rewrite the same
        # values — their trajectories are copies of a live row's)
        harvest(done[~harvested[perm[done]]])
        if live.size == 0 or total >= max_steps:
            if live.size:
                harvest(live)                # truncated at the step budget
            break
        cur_Q = int(qs.shape[0])
        bucket = min(max(min_bucket, _next_pow2(live.size)), cur_Q)
        if bucket < cur_Q:
            pad = bucket - live.size
            idx = np.concatenate([live, live[:1].repeat(pad)]) if pad \
                else live
            idx_dev = jnp.asarray(idx)
            qs, ver, nodes, state = _gather_rows((qs, ver, nodes, state),
                                                 idx_dev)
            perm = perm[idx]
        limit = jnp.asarray(min(chunk, max_steps - total), jnp.int32)
        with obs.span("chunk") as csp:
            state, active, ran = _graph_chunk(arrays, qs, ver, nodes, state,
                                              limit, fanout=fanout, **kw)
            ran = int(ran)
            active_h = np.asarray(active)
            if obs.tracing():
                csp.set("rows", int(qs.shape[0])).set("live", int(live.size))
                csp.set("steps", ran)
                csp.set("evals_executed", int(qs.shape[0]) * ran * fanout * S)
        total += ran
        executed_row_steps += int(qs.shape[0]) * ran

    if obs.tracing():
        u = int(conv_steps.sum())
        obs.span("wavefront_totals").set("steps", total) \
            .set("evals_executed", executed_row_steps * fanout * S) \
            .set("evals_useful", u * fanout * S).stop()
    if not with_stats:
        return out_ids, out_d
    useful = int(conv_steps.sum())
    stats = {
        "steps": total,
        "conv_steps": conv_steps,
        "evals_executed": executed_row_steps * fanout * S,
        "evals_useful": useful * fanout * S,
        "wasted_eval_frac": (1.0 - useful / executed_row_steps
                             if executed_row_steps else 0.0),
    }
    return out_ids, out_d, stats


# ---- continuous-batching stream (slot refill between chunks) ---------------

def _tree_concat_rows(a, b):
    """Concatenate two state pytrees along the row axis; scalar leaves (the
    step counter) keep ``a``'s value — the counter only bounds chunk length,
    never a row's trajectory."""
    return jax.tree_util.tree_map(
        lambda x, y: x if x.ndim == 0 else jnp.concatenate([x, y], axis=0),
        a, b)


@jax.jit
def _refill_rows(old, new, idx):
    """Admit a newcomer block into a live batch: concat along rows, then
    gather ``idx`` — fused in ONE compiled computation. Eager per-leaf
    concatenates would each compile per (live, newcomer) shape pair, and
    those pairs depend on arrival timing, so a serving process would keep
    hitting fresh compiles mid-flight; fused, the retrace space is the
    power-of-two (old bucket, new block, out bucket) triples."""
    cat = _tree_concat_rows(old, new)
    return jax.tree_util.tree_map(
        lambda a: a if a.ndim == 0 else a[idx], cat)


class WavefrontStream:
    """Continuous-batching wavefront driver over one MSTG variant.

    The chunked driver (:func:`mstg_graph_search_chunked`) compacts converged
    rows *out* of the active batch; this driver additionally admits **newly
    arrived** queries *into* the freed slots between chunks — true continuous
    batching: the device batch stays near-full while individual queries enter
    and leave mid-flight.

    Correctness contract: per-row trajectories are independent (the step body
    is the identity for converged rows, and init/distance/merge are all
    row-local), so every query's ``(ids, dists)`` is **bit-identical** to
    running it alone through :func:`mstg_graph_search` /
    :func:`mstg_graph_search_chunked` with the same ``ef`` / ``fanout`` /
    ``packed`` / ``use_kernel`` / ``max_steps`` — regardless of which other
    queries shared its batch or when it was admitted (property-tested in
    ``tests/test_serving_async.py``).

    Usage::

        stream = WavefrontStream(dv.tree(), ef=64, Kpad=dv.meta.Kpad)
        stream.admit(tags, queries, version, key_lo, key_hi, max_steps=320)
        while not stream.idle:
            for tag, ids, dists, steps in stream.step():
                ...   # one converged (or budget-truncated) query

    ``tags`` are opaque non-negative ints the caller uses to route results;
    harvested rows return the full ``ef``-wide beam (slice ``[:k]`` for a
    request's k — a prefix slice, so per-request k costs nothing).

    Batch mechanics: rows live in power-of-two buckets (jit-cache reuse,
    same policy as the engine); ``max_bucket`` caps rows in flight and must
    be a power of two. Padding rows are empty-task or duplicated rows with
    ``tag -1`` — never harvested. The per-chunk step budget is
    ``min(chunk, min remaining budget over live rows)`` so a truncated query
    stops at *exactly* its ``max_steps``, matching solo execution bit for
    bit.

    Occupancy / refill accounting for the serving metrics layer:
    ``executed_row_steps`` (slots x steps paid), ``useful_row_steps``
    (per-row convergence steps actually needed), ``refills`` /
    ``refilled_rows`` (admissions into an already-running batch),
    ``occupancy_rows`` / ``occupancy_capacity`` (live rows vs bucket width
    summed per chunk).
    """

    def __init__(self, arrays: dict, *, ef: int, Kpad: int,
                 use_kernel: bool = False, fanout: int = 1, chunk: int = 16,
                 min_bucket: int = 8, max_bucket: int = 256,
                 packed: bool = True):
        if max_bucket < 1 or (max_bucket & (max_bucket - 1)):
            raise ValueError(f"max_bucket must be a power of two, got "
                             f"{max_bucket}")
        self.arrays = arrays
        self.ef = int(ef)
        self.fanout = max(1, int(fanout))
        self.chunk = max(1, int(chunk))
        self.min_bucket = min(int(min_bucket), max_bucket)
        self.max_bucket = int(max_bucket)
        self._kw = dict(ef=self.ef, Kpad=int(Kpad),
                        use_kernel=bool(use_kernel), packed=bool(packed))
        # pending admissions (host-side, FIFO)
        self._pending: list = []
        # in-flight device state; perm -1 marks pad/dead rows
        self._qs = self._ver = self._nodes = self._state = None
        self._perm = np.zeros(0, np.int64)
        self._steps_run = np.zeros(0, np.int64)
        self._budget = np.zeros(0, np.int64)
        self._active = np.zeros(0, bool)
        # cumulative counters (serving metrics)
        self.admitted = 0
        self.completed = 0
        self.refills = 0
        self.refilled_rows = 0
        self.chunks = 0
        self.executed_row_steps = 0
        self.useful_row_steps = 0
        self.occupancy_rows = 0
        self.occupancy_capacity = 0

    # ---- introspection ----
    @property
    def inflight(self) -> int:
        """Real (tagged) rows currently in the device batch."""
        return int((self._perm >= 0).sum())

    @property
    def n_pending(self) -> int:
        return len(self._pending)

    @property
    def idle(self) -> bool:
        return not self._pending and self.inflight == 0

    @property
    def refill_efficiency(self) -> float:
        """useful / executed row-steps (1.0 = every paid slot-step advanced
        an unconverged query)."""
        if not self.executed_row_steps:
            return 1.0
        return self.useful_row_steps / self.executed_row_steps

    # ---- admission ----
    def admit(self, tags, queries, version, key_lo, key_hi,
              max_steps) -> None:
        """Queue rows for admission at the next :meth:`step`. One entry per
        row; ``max_steps`` is scalar or per-row."""
        queries = np.ascontiguousarray(queries, np.float32)
        tags = np.asarray(tags, np.int64).ravel()
        version = np.asarray(version, np.int64).ravel()
        key_lo = np.asarray(key_lo, np.int64).ravel()
        key_hi = np.asarray(key_hi, np.int64).ravel()
        budget = np.broadcast_to(np.asarray(max_steps, np.int64),
                                 tags.shape).copy()
        if np.any(tags < 0):
            raise ValueError("tags must be >= 0 (-1 is the pad sentinel)")
        if np.any(budget < 1):
            raise ValueError("max_steps must be >= 1")
        for i in range(tags.shape[0]):
            self._pending.append((int(tags[i]), queries[i], int(version[i]),
                                  int(key_lo[i]), int(key_hi[i]),
                                  int(budget[i])))
        self.admitted += int(tags.shape[0])

    # ---- internals ----
    def _init_new(self, count: int):
        """Pop ``count`` pending rows, init their state padded to a
        power-of-two block (pad rows carry empty tasks: version -1,
        key_lo > key_hi — converged before their first step)."""
        rows = self._pending[:count]
        del self._pending[:count]
        Nb = max(self.min_bucket, _next_pow2(count))
        pad = Nb - count
        d = rows[0][1].shape[0]
        q = np.zeros((Nb, d), np.float32)
        ver = np.full(Nb, -1, np.int64)
        klo = np.ones(Nb, np.int64)
        khi = np.zeros(Nb, np.int64)
        perm = np.full(Nb, -1, np.int64)
        budget = np.zeros(Nb, np.int64)
        for i, (tag, qv, v, lo, hi, b) in enumerate(rows):
            q[i], ver[i], klo[i], khi[i] = qv, v, lo, hi
            perm[i], budget[i] = tag, b
        qs = jnp.asarray(q)
        vj = jnp.asarray(ver, jnp.int32)
        nodes, state, active = _graph_init(
            self.arrays, qs, vj, jnp.asarray(klo, jnp.int32),
            jnp.asarray(khi, jnp.int32), **self._kw)
        return (qs, vj, nodes, state, np.asarray(active), perm, budget,
                np.zeros(Nb, np.int64), pad)

    def _compose(self) -> bool:
        """Drop dead rows, admit pending ones into the freed slots, and
        repack to a power-of-two bucket. Returns True when a runnable batch
        exists."""
        keep_mask = ((self._perm >= 0) & self._active
                     & (self._steps_run < self._budget))
        keep = np.flatnonzero(keep_mask)
        n_live = keep.size
        n_new = min(len(self._pending), max(0, self.max_bucket - n_live))
        if n_live == 0 and n_new == 0:
            self._qs = self._ver = self._nodes = self._state = None
            self._perm = np.zeros(0, np.int64)
            self._active = np.zeros(0, bool)
            return False
        if n_new == 0:
            # no admissions: rebucket only when shrinking pays or a live-but-
            # finished (budget-exhausted) row must be evicted; dead inactive
            # rows ride along as identity steps, exactly like the chunked
            # driver's compaction policy
            cur = self._perm.shape[0]
            bucket = min(max(self.min_bucket, _next_pow2(n_live)), cur)
            zombies = bool(np.any(self._active & ~keep_mask))
            if bucket == cur and not zombies:
                return True
            idx, n_pad = self._pad_idx(keep, bucket,
                                       np.flatnonzero(~self._active))
            self._gather(idx, n_pad)
            return True
        if n_live:
            self.refills += 1
            self.refilled_rows += n_new
        (nqs, nver, nnodes, nstate, nactive, nperm, nbudget, nsteps,
         n_pad) = self._init_new(n_new)
        if n_live == 0:
            # nothing in flight survives: adopt the newcomer block as-is
            self._qs, self._ver = nqs, nver
            self._nodes, self._state = nnodes, nstate
            self._active, self._perm = nactive, nperm
            self._budget, self._steps_run = nbudget, nsteps
            return True
        # gather (kept live rows | newcomer rows | pads) from the virtual
        # concat [old; newcomer block] in one fused device call
        old_rows = self._perm.shape[0]
        active = np.concatenate([self._active, nactive])
        perm = np.concatenate([self._perm, nperm])
        budget = np.concatenate([self._budget, nbudget])
        steps = np.concatenate([self._steps_run, nsteps])
        bucket = max(self.min_bucket, _next_pow2(n_live + n_new))
        take = np.concatenate([keep, old_rows + np.arange(n_new)])
        idx, n_pad = self._pad_idx(take, bucket, np.flatnonzero(~active))
        self._qs, self._ver, self._nodes, self._state = _refill_rows(
            (self._qs, self._ver, self._nodes, self._state),
            (nqs, nver, nnodes, nstate), jnp.asarray(idx))
        self._active = active[idx]
        perm = perm[idx]
        if n_pad:
            perm[idx.size - n_pad:] = -1
        self._perm = perm
        self._budget = budget[idx]
        self._steps_run = steps[idx]
        return True

    @staticmethod
    def _pad_idx(take: np.ndarray, bucket: int, inactive: np.ndarray):
        """Row-index vector of length ``bucket``: the kept rows plus pad
        slots. Pads point at an inactive source row when one exists (zero
        marginal work: converged rows run the identity), else duplicate the
        first kept row. Returns ``(idx, n_pad)``."""
        pad = bucket - take.size
        if pad <= 0:
            return take, 0
        src = inactive[0] if inactive.size else take[0]
        return np.concatenate([take, np.full(pad, src, np.int64)]), pad

    def _gather(self, idx: np.ndarray, n_pad: int) -> None:
        idx_dev = jnp.asarray(idx)
        self._qs, self._ver, self._nodes, self._state = _gather_rows(
            (self._qs, self._ver, self._nodes, self._state), idx_dev)
        self._active = self._active[idx]
        perm = self._perm[idx]
        if n_pad:
            perm[idx.size - n_pad:] = -1
        self._perm = perm
        self._budget = self._budget[idx]
        self._steps_run = self._steps_run[idx]

    # ---- the serving loop entry point ----
    def step(self):
        """Compose (drop converged + refill from pending), run one chunk,
        and harvest rows that converged or exhausted their budget.

        Returns a list of ``(tag, ids, dists, steps)`` — ids/dists are the
        full ``ef``-wide beam (NO_EDGE / +inf padded), steps the row's
        convergence (or truncation) step count.
        """
        with obs.span("chunk") as csp:
            if not self._compose():
                return []
            real = self._perm >= 0
            live = real & self._active & (self._steps_run < self._budget)
            remaining = self._budget[live] - self._steps_run[live]
            limit = min(self.chunk, int(remaining.min())) if remaining.size \
                else self.chunk
            bucket = self._perm.shape[0]
            self.occupancy_rows += int(live.sum())
            self.occupancy_capacity += bucket
            self._state, active, ran = _graph_chunk(
                self.arrays, self._qs, self._ver, self._nodes, self._state,
                jnp.asarray(limit, jnp.int32), fanout=self.fanout, **self._kw)
            ran = int(ran)
            self._active = np.asarray(active)
            self._steps_run = self._steps_run + ran
            self.chunks += 1
            self.executed_row_steps += bucket * ran
            # harvest: converged, or truncated at exactly their step budget
            done = np.flatnonzero(real & (~self._active
                                          | (self._steps_run >= self._budget)))
            if obs.tracing():
                csp.set("live", int(live.sum())).set("bucket", bucket)
                csp.set("steps", ran).set("harvested", int(done.size))
                csp.set("occupancy", round(int(live.sum()) / bucket, 4))
            if done.size == 0:
                return []
            ids_h, d_h, steps_h = _harvest(self._state, done, self.ef)
            out = [(int(self._perm[r]), ids_h[j], d_h[j], int(steps_h[j]))
                   for j, r in enumerate(done)]
            self._perm[done] = -1
            self.completed += done.size
            self.useful_row_steps += int(steps_h.sum())
            return out

    def drain(self):
        """Run :meth:`step` until idle; returns every harvested row."""
        out = []
        while not self.idle:
            out.extend(self.step())
        return out


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(ids_a, d_a, ids_b, d_b, k: int):
    """Merge two (Q, k) result sets, dropping duplicate ids (Theorem 4.1 plans
    may overlap at predicate boundaries). Jitted: the engine calls it on
    device arrays between plan slots."""
    ids = jnp.concatenate([ids_a, ids_b], axis=1)
    d = jnp.concatenate([d_a, d_b], axis=1)
    order = jnp.argsort(d, axis=1)
    ids = jnp.take_along_axis(ids, order, 1)
    d = jnp.take_along_axis(d, order, 1)
    # mark duplicates of any earlier (closer) id
    dup = (ids[:, :, None] == ids[:, None, :])
    earlier = jnp.tril(jnp.ones((ids.shape[1], ids.shape[1]), bool), k=-1)
    is_dup = jnp.any(dup & earlier[None] & (ids[:, None, :] != NO_EDGE), axis=2)
    d = jnp.where(is_dup, INF, d)
    ids = jnp.where(is_dup, NO_EDGE, ids)
    order = jnp.argsort(d, axis=1)[:, :k]
    return jnp.take_along_axis(ids, order, 1), jnp.take_along_axis(d, order, 1)


# The host-facing graph-path API is QueryEngine (repro.core.engine) with
# route="graph"; this module keeps the device-level pieces.
