"""Fast API-surface smoke check (not-slow CI lane): the declared public
surface of repro.core imports, __all__ is complete and resolvable, and the
core request/predicate types construct without touching an index."""
import numpy as np


def test_core_all_resolves():
    import repro.core as core
    assert core.__all__, "repro.core must declare __all__"
    missing = [name for name in core.__all__ if not hasattr(core, name)]
    assert not missing, f"__all__ names missing from repro.core: {missing}"
    # star-import view == __all__ (no stale or shadowed exports)
    ns = {}
    exec("from repro.core import *", ns)
    exported = {k for k in ns if not k.startswith("__")}
    assert exported == set(core.__all__)


def test_key_surface_types_construct():
    from repro.core import (Overlaps, Predicate, IndexSpec, QueryHit,
                            SearchRequest, SearchResult, parse_mask)
    req = SearchRequest(np.zeros((2, 4), np.float32),
                        (np.zeros(2), np.ones(2)), Overlaps(), k=3)
    assert len(req) == 2 and req.mask == 15
    res = SearchResult(np.full((2, 3), -1, np.int32),
                       np.full((2, 3), np.inf, np.float32))
    assert len(res) == 2 and not res.valid_mask.any()
    assert isinstance(res[0], QueryHit)
    assert parse_mask("any_overlap") == Predicate.parse("1|2|3|4").mask
    assert IndexSpec().predicate == Overlaps()


def test_engine_config_and_shard_report_construct():
    from repro.core import EngineConfig, ShardReport
    cfg = EngineConfig(route="pruned", sel_cache_max=16)
    assert cfg.route == "pruned"
    assert cfg.replace(route="auto").route == "auto"
    rep = ShardReport(shard=3, n=100, route="lost", alive=False)
    assert rep.shard == 3 and not rep.alive


def test_distributed_surface_imports():
    from repro.distributed import (DeploymentSpec, HeartbeatRegistry,
                                   MERGE_SCHEDULES, ShardedDeployment,
                                   resolve_merge,
                                   sharded_flat_topk)  # noqa: F401
    assert set(MERGE_SCHEDULES) == {"all_gather", "tournament"}
    assert resolve_merge("auto", 4) == "all_gather"
    assert resolve_merge("auto", 16) == "tournament"
    spec = DeploymentSpec(n_shards=4, per_shard_k=5)
    assert spec.replace(merge="tournament").merge == "tournament"


def test_serving_and_checkpoint_surface_imports():
    from repro.serving import RetrievalServer, ServeEngine  # noqa: F401
    from repro.checkpoint import IndexIOError, index_io
    assert callable(index_io.save_npz_atomic) and callable(index_io.load_npz)
    assert issubclass(IndexIOError, ValueError)


def test_streaming_surface_imports():
    import repro.streaming as streaming
    missing = [n for n in streaming.__all__ if not hasattr(streaming, n)]
    assert not missing
    from repro.core import SegmentReport
    from repro.streaming import CompactionPolicy, SegmentedIndex
    assert CompactionPolicy().pick([]) == []
    s = SegmentedIndex()
    assert len(s) == 0 and 0 not in s and s.stats()["segments"] == []
    assert SegmentReport("delta", 0, "delta", 0).tombstones == 0
