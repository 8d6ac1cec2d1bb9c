"""Compile-only checks for a TPU v5e at real widths.

The kernels and the exact flat scan are lowered and compiled against a
described (not attached) ``v5e:2x2`` topology: nothing runs, but whatever
the chip's compiler would refuse (a block not aligned to the tiling, too
much VMEM, a program that does not fit HBM) fails here. The topology is
described inside a module fixture, never while a module is imported, and
the persistent compilation cache is off around these compiles (an entry
written for a described chip cannot be read back without one).
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import ANY_OVERLAP, EngineConfig, QueryEngine, SearchRequest
from repro.core.flat import flat_search
from repro.data import make_queries
from repro.kernels import fused_topk, gathered_l2, pairwise_l2, pairwise_l2_int8

Q, N, D, K = 256, 1_000_000, 128, 10
HBM = 16e9                                 # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM
    return compiled


def _endpoints(spec, n):
    return spec((n,)), spec((n,))


def test_flat_search_compiles(spec):
    """The jnp exact scan at Q=256 over a 1M x 128 corpus fits one chip."""
    _compile(functools.partial(flat_search, mask=ANY_OVERLAP, k=K),
             spec((N, D)), *_endpoints(spec, N), spec((Q, D)),
             *_endpoints(spec, Q))


@pytest.mark.parametrize("d", [128, 768])
def test_pairwise_l2_masked_compiles(spec, d):
    c = _compile(functools.partial(pairwise_l2.pairwise_l2_masked,
                                   mask=ANY_OVERLAP),
                 spec((Q, d)), spec((N, d)), *_endpoints(spec, N),
                 *_endpoints(spec, Q))
    assert "tpu_custom_call" in c.as_text()


def test_pairwise_l2_masked_compiles_unaligned(spec):
    """Q and N off the block grid pad to tile-aligned blocks."""
    c = _compile(functools.partial(pairwise_l2.pairwise_l2_masked,
                                   mask=ANY_OVERLAP),
                 spec((37, D)), spec((1000, D)), *_endpoints(spec, 1000),
                 *_endpoints(spec, 37))
    assert "tpu_custom_call" in c.as_text()


def test_pairwise_l2_int8_compiles(spec):
    c = _compile(functools.partial(pairwise_l2_int8.pairwise_l2_int8,
                                   mask=ANY_OVERLAP),
                 spec((Q, D)), spec((N, D), jnp.int8), spec((D,)), spec((D,)),
                 spec((N,)), *_endpoints(spec, N), *_endpoints(spec, Q))
    assert "tpu_custom_call" in c.as_text()


def test_fused_topk_l2_compiles(spec):
    c = _compile(functools.partial(fused_topk.fused_topk_l2,
                                   mask=ANY_OVERLAP, k=K),
                 spec((Q, D)), spec((N, D)), *_endpoints(spec, N),
                 *_endpoints(spec, Q))
    assert "tpu_custom_call" in c.as_text()


def test_gathered_l2_compiles(spec):
    """One wavefront step's candidate distances: fanout 4 x 74 slots."""
    c = _compile(gathered_l2.gathered_l2, spec((Q, D)), spec((Q, 296, D)))
    assert "tpu_custom_call" in c.as_text()


def test_engine_refuses_graph_kernel_on_tpu(small_ds, built_index,
                                            monkeypatch):
    """gathered_topk holds the whole (n, d) table as one VMEM block, which a
    real corpus cannot fit: on a TPU the engine refuses the graph route
    with use_kernel=True instead of swapping in another path."""
    import repro.core.engine as engine_mod
    monkeypatch.setattr(engine_mod, "_backend", lambda: "tpu")
    eng = QueryEngine(built_index, config=EngineConfig(use_kernel=True))
    qlo, qhi = make_queries(small_ds, ANY_OVERLAP, 0.2, seed=3)
    req = SearchRequest(small_ds.queries, (qlo, qhi), ANY_OVERLAP, k=K,
                        route="graph")
    with pytest.raises(NotImplementedError, match="VMEM"):
        eng.execute(req)
    # the exact routes keep their kernels
    flat = eng.execute(SearchRequest(small_ds.queries, (qlo, qhi),
                                     ANY_OVERLAP, k=K, route="flat"))
    assert np.asarray(flat.ids).shape == (len(small_ds.queries), K)
