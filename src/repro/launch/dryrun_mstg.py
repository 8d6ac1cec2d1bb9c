import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Dry-run + roofline for the PAPER's serving step itself: distributed
RR-filtered top-k (MSTG flat engine) over a pod-scale corpus.

Corpus sharded over 'data' (and 'pod'), queries replicated, per-shard fused
predicate+distance + top-k, tournament/all-gather merge. Lowered with
ShapeDtypeStructs only; costs are exact (no scan bodies).

  PYTHONPATH=src python -m repro.launch.dryrun_mstg
"""
import argparse
import functools
import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ANY_OVERLAP
from repro.core.flat import flat_search
from repro.core.hnsw import NO_EDGE
from repro.distributed.topk import global_topk_merge, tournament_topk_merge
from repro.launch.dryrun import ARTIFACT_DIR, collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.obs.profile import V5E, peaks

# production serving shape: 1M corpus x 1024-query batch, d=128 (SIFT-like)
N_CORPUS = 1 << 20
N_QUERIES = 1024
DIM = 128
K = 10


def build_step(mesh, merge: str, mask: int = ANY_OVERLAP, k: int = K):
    corpus_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    D = int(np.prod([mesh.shape[a] for a in corpus_axes]))
    nloc = N_CORPUS // D
    merge_fn = {"all_gather": global_topk_merge,
                "tournament": tournament_topk_merge}[merge]
    # flatten (pod, data) into one logical shard axis via nested merges
    ax = corpus_axes[-1]

    # corpus over (pod, data); queries over 'model' — every device does
    # (Q/model) x (N/(pod*data)) distance work, the full-mesh decomposition
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(corpus_axes, None), P(corpus_axes), P(corpus_axes),
                  P("model", None), P("model"), P("model")),
        out_specs=(P("model", None), P("model", None)),
        check_vma=False)
    def run(c, l, h, q, a, b):
        ids, d = flat_search(c, l, h, q, a, b, mask=mask, k=k)
        idx = jax.lax.axis_index(corpus_axes[0])
        if len(corpus_axes) > 1:
            idx = idx * mesh.shape[corpus_axes[1]] + jax.lax.axis_index(
                corpus_axes[1])
        gids = jnp.where(ids != NO_EDGE, ids + idx * nloc, NO_EDGE)
        gids, d = merge_fn(gids, d, k, ax)
        if len(corpus_axes) > 1:
            gids_all = jax.lax.all_gather(gids, corpus_axes[0])
            d_all = jax.lax.all_gather(d, corpus_axes[0])
            Dp = gids_all.shape[0]
            gids = jnp.moveaxis(gids_all, 0, 1).reshape(gids.shape[0], Dp * k)
            d2 = jnp.moveaxis(d_all, 0, 1).reshape(d.shape[0], Dp * k)
            neg, pos = jax.lax.top_k(-d2, k)
            gids = jnp.take_along_axis(gids, pos, 1)
            d = -neg
        return gids, d

    args = (jax.ShapeDtypeStruct((N_CORPUS, DIM), jnp.float32),
            jax.ShapeDtypeStruct((N_CORPUS,), jnp.float32),
            jax.ShapeDtypeStruct((N_CORPUS,), jnp.float32),
            jax.ShapeDtypeStruct((N_QUERIES, DIM), jnp.float32),
            jax.ShapeDtypeStruct((N_QUERIES,), jnp.float32),
            jax.ShapeDtypeStruct((N_QUERIES,), jnp.float32))
    return run, args


def build_step_v2(mesh, mask: int = ANY_OVERLAP, k: int = K):
    """§Perf iteration 6 layout: corpus over the FULL mesh, queries
    replicated, blocked fused top-k (no HBM distance matrix), hierarchical
    tournament merge. Arithmetic intensity per corpus byte rises from
    2·(Q/model) to 2·Q — past the v5e knee."""
    from repro.core.flat import flat_search_blocked
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    Dall = int(np.prod([mesh.shape[a] for a in axes]))
    nloc = N_CORPUS // Dall

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(axes),
                  P(None, None), P(None), P(None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)
    def run(c, l, h, q, a, b):
        ids, d = flat_search_blocked(c, l, h, q, a, b, mask=mask, k=k)
        idx = jnp.zeros((), jnp.int32)
        for ax in axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        gids = jnp.where(ids != NO_EDGE, ids + idx * nloc, NO_EDGE)
        d_out, i_out = d, gids
        for ax in reversed(axes):  # butterfly per axis, innermost first
            i_out, d_out = tournament_topk_merge(i_out, d_out, k, ax)
        return i_out, d_out

    args = (jax.ShapeDtypeStruct((N_CORPUS, DIM), jnp.float32),
            jax.ShapeDtypeStruct((N_CORPUS,), jnp.float32),
            jax.ShapeDtypeStruct((N_CORPUS,), jnp.float32),
            jax.ShapeDtypeStruct((N_QUERIES, DIM), jnp.float32),
            jax.ShapeDtypeStruct((N_QUERIES,), jnp.float32),
            jax.ShapeDtypeStruct((N_QUERIES,), jnp.float32))
    return run, args


def run_cell(mesh_kind: str, merge: str, artifact_dir: str, force=False):
    cell = f"mstg-flat-serve__{merge}__{mesh_kind}"
    path = os.path.join(artifact_dir, cell + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    ndev = int(np.prod(list(mesh.shape.values())))
    if merge == "fullmesh_v2":
        fn, args = build_step_v2(mesh)
    else:
        fn, args = build_step(mesh, merge)
    t0 = time.time()
    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    colls, wire, counts = collective_bytes(compiled.as_text(), ndev)
    flops = float(ca.get("flops", 0))
    nbytes = float(ca.get("bytes accessed", 0))
    rec = {
        "cell": cell, "status": "ok", "devices": ndev, "merge": merge,
        "corpus": N_CORPUS, "queries": N_QUERIES, "dim": DIM, "k": K,
        "compile_s": round(time.time() - t0, 1),
        "flops_per_device": flops, "bytes_per_device": nbytes,
        "memory": {"temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                   "argument_bytes": getattr(mem, "argument_size_in_bytes", None)},
        "collective_bytes": colls, "collective_wire_bytes": wire,
        "collective_counts": counts,
        "terms": {"compute_s": flops / peaks(V5E)["flops"],
                  "memory_hlo_s": nbytes / peaks(V5E)["hbm_bw"],
                  "collective_s": sum(colls.values()) / peaks(V5E)["link_bw"]},
        # model flops per device: Q_loc x N_loc masked distances
        "model_flops_per_device": (
            N_QUERIES * (N_CORPUS / ndev) * 2 * DIM if merge == "fullmesh_v2"
            else (N_QUERIES / mesh.shape["model"]) *
                 (N_CORPUS * mesh.shape["model"] / ndev) * 2 * DIM),
    }
    os.makedirs(artifact_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    t = rec["terms"]
    print(f"[ok] {cell}: flops/dev {flops:.3e} compute {t['compute_s']*1e3:.3f}ms "
          f"mem-ub {t['memory_hlo_s']*1e3:.3f}ms coll {t['collective_s']*1e3:.4f}ms "
          f"counts={ {k: v for k, v in counts.items() if v} }")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default=ARTIFACT_DIR)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    for mesh_kind in ("single_pod", "multi_pod"):
        for merge in ("all_gather", "tournament", "fullmesh_v2"):
            run_cell(mesh_kind, merge, args.artifacts, force=args.force)


if __name__ == "__main__":
    main()
