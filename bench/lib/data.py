"""Corpus and query data, made from the run's seed.

Vectors follow the repository's synthetic protocol (paper section 5):
a mixture of Gaussians, ``centers[c] + 0.35 * N(0, 1)`` with centres drawn
from N(0, 1). They are made on the device, one jitted program for every
chunk of rows, and each chunk is brought to the host as it is made, where
the index is built from them.

Object intervals copy the repository's generator: an anchor ``a`` drawn
from the configured distribution over ``[0, span)``, a width
``w ~ U(0, max_width_frac * span)`` on either side or both, clipped to the
span, and endpoints snapped up to a grid of ``grid`` values (the paper's
finite attribute domain, Exp. 10's |A|).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

NOISE = 0.35


@dataclasses.dataclass
class Corpus:
    vectors: np.ndarray    # (n, d) float32
    lo: np.ndarray         # (n,) float64, values on the grid
    hi: np.ndarray
    lo_pos: np.ndarray     # (n,) grid positions of lo / hi
    hi_pos: np.ndarray
    grid: np.ndarray       # (K,) the attribute domain
    queries: np.ndarray    # (P, d) float32 query vectors


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Four 32-bit words for one named stream of a run's seed. Any integer
    seed, however large, maps to distinct words."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0), stream]
                                  ).generate_state(4, np.uint32)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream))


def _chunks(n: int, limit: int = 1 << 16) -> int:
    """Smallest chunk count whose chunks divide n and hold at most limit."""
    c = max(1, -(-n // limit))
    while n % c:
        c += 1
    return c


@functools.lru_cache(maxsize=None)
def _vector_programs(rows: int, d: int, clusters: int, n_queries: int):
    """Jitted (chunk of corpus rows, query vectors) makers. One program
    makes every chunk, so a chunk's buffer is all the device holds."""
    import jax
    import jax.numpy as jnp

    def keys(key_words):
        key = jax.random.wrap_key_data(key_words, impl="threefry2x32")
        kc, kv, kqa, kq = jax.random.split(key, 4)
        return jax.random.normal(kc, (clusters, d), jnp.float32), kv, kqa, kq

    def chunk(key_words, i):
        centers, kv, _, _ = keys(key_words)
        k1, k2 = jax.random.split(jax.random.fold_in(kv, i))
        a = jax.random.randint(k1, (rows,), 0, clusters)
        return centers[a] + NOISE * jax.random.normal(k2, (rows, d),
                                                      jnp.float32)

    def queries(key_words):
        centers, _, kqa, kq = keys(key_words)
        a = jax.random.randint(kqa, (n_queries,), 0, clusters)
        return centers[a] + NOISE * jax.random.normal(kq, (n_queries, d),
                                                      jnp.float32)

    return jax.jit(chunk), jax.jit(queries)


def make_vectors(seed: int, n: int, d: int, clusters: int, n_queries: int):
    """(corpus (n, d), queries (n_queries, d)) as host float32 arrays."""
    n_chunks = _chunks(n)
    rows = n // n_chunks
    chunk, queries = _vector_programs(rows, d, clusters, n_queries)
    words = seed_words(seed, 0)[:2]
    out = np.empty((n, d), np.float32)
    for i in range(n_chunks):
        out[i * rows:(i + 1) * rows] = np.asarray(chunk(words, i))
    return out, np.asarray(queries(words))


def _anchors(n: int, dist: str, span: float, rng: np.random.Generator):
    if dist == "uniform":
        return rng.uniform(0, span, n)
    if dist == "normal":
        return np.clip(rng.normal(span / 2, span / 6, n), 0, span)
    raise ValueError(f"unknown attribute distribution {dist!r}")


def make_intervals(seed: int, n: int, *, dist: str, span: float,
                   max_width_frac: float, grid: int):
    rng = host_rng(seed, 1)
    a = _anchors(n, dist, span, rng)
    w = rng.uniform(0, span * max_width_frac, n)
    lo = np.minimum(a, np.clip(a + w * rng.choice([-1, 1], n), 0, span))
    hi = np.maximum(a, np.clip(a + w * rng.choice([-1, 1], n), 0, span))
    values = np.linspace(0, span, grid)
    lo_pos = np.clip(np.searchsorted(values, lo), 0, grid - 1)
    hi_pos = np.clip(np.searchsorted(values, hi), 0, grid - 1)
    lo_pos, hi_pos = np.minimum(lo_pos, hi_pos), np.maximum(lo_pos, hi_pos)
    return values[lo_pos], values[hi_pos], lo_pos, hi_pos, values


def make_corpus(cfg: dict, seed: int, n_queries: int) -> Corpus:
    vecs, qv = make_vectors(seed, cfg["n"], cfg["d"], cfg["clusters"],
                            n_queries)
    lo, hi, lo_pos, hi_pos, values = make_intervals(
        seed, cfg["n"], dist=cfg["attribute_dist"], span=cfg["span"],
        max_width_frac=cfg["max_width_frac"], grid=cfg["grid"])
    return Corpus(vecs, lo, hi, lo_pos, hi_pos, values, qv)
