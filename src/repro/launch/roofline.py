import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Roofline analysis (deliverable g) from dry-run artifacts.

Terms per (arch x shape), single-pod mesh, TPU v5e constants:

    compute    = HLO_FLOPs_per_device / 197e12
    memory     = HLO_bytes_per_device / 819e9
    collective = collective_operand_bytes_per_device / 50e9

Scan-count correction: XLA's cost_analysis counts a ``lax.scan`` body ONCE
regardless of trip count. We therefore re-lower each cell twice per segment
with `scan_layers=False` (unrolled) tiny-depth variants — base (all segments
repeat=1) and per-segment bump (repeat=2) — whose difference is the exact
per-layer cost; corrected totals add (repeats-1) x unit to the full compile's
numbers. MODEL_FLOPS uses 6·N·D (train) / 2·N_active·tokens (serve).

Usage: PYTHONPATH=src python -m repro.launch.roofline [--arch A] [--shape S]
"""
import argparse
import dataclasses
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, ALL_SHAPES, SHAPES_BY_NAME, get_config
from repro.launch.dryrun import ARTIFACT_DIR, collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import ArchRunner
from repro.models.transformer import LM

# chip constants live in repro.obs.profile so kernel trace spans and this
# analytic model agree on the same peaks; re-exported here for callers.
from repro.obs.profile import V5E, peaks

ROOF_DIR = os.environ.get("ROOFLINE_ARTIFACTS",
                          os.path.join(os.path.dirname(ARTIFACT_DIR), "roofline"))


def _measure(cfg, shape_name, mesh, repeats):
    # unrolled layers AND unrolled flash blocks (big chunks keep the HLO
    # small) so cost_analysis sees every scanned body — incl. the true
    # S^2 attention work with causal/window block-skipping (§Perf iter. 7)
    seq = SHAPES_BY_NAME[shape_name].seq_len
    chunk = max(min(seq // 4, 8192), 128)
    runner = ArchRunner(dataclasses.replace(cfg, scan_layers=False,
                                            flash_unroll=True,
                                            q_chunk=chunk, kv_chunk=chunk),
                        mesh, segment_repeats=tuple(repeats))
    bundle = runner.bundle_for(SHAPES_BY_NAME[shape_name])
    with mesh:
        compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                           out_shardings=bundle.out_shardings,
                           donate_argnums=bundle.donate
                           ).lower(*bundle.args).compile()
    ca = compiled.cost_analysis() or {}
    ndev = int(np.prod(list(mesh.shape.values())))
    colls, wire, _ = collective_bytes(compiled.as_text(), ndev)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(sum(colls.values())),
            "wire": float(sum(wire.values()))}


def _sub(a, b):
    return {k: max(a[k] - b[k], 0.0) for k in a}


def analytic_memory_bytes(cfg, lm: LM, shape, mesh_shape) -> float:
    """First-principles per-device HBM traffic estimate (documented ±2x).

    XLA-CPU's ``bytes accessed`` counts every unfused operand — a large upper
    bound relative to a TPU compile. This model instead counts what a fused
    TPU program must move: weight reads (post-FSDP-gather, so TP-sharded
    only; x3 for fwd/bwd/remat in training), optimizer/gradient traffic on
    the fully-sharded copies, a per-layer activation constant, logits chunks,
    and KV-cache traffic for serving."""
    dp = int(np.prod([v for k, v in mesh_shape.items() if k != "model"]))
    mp = int(mesh_shape.get("model", 1))
    devices = dp * mp
    pb = jnp.dtype(cfg.param_dtype).itemsize
    ab = jnp.dtype(cfg.activ_dtype).itemsize
    n_params = lm.param_count()
    n_active = lm.active_param_count()
    P_tp = n_params * pb / mp          # per-device weight bytes after gather
    P_dev = n_params * pb / devices    # fully-sharded (FSDP) weight bytes
    B_loc = max(shape.global_batch // dp, 1)
    L = cfg.n_layers + cfg.n_enc_layers
    D = cfg.d_model
    F = (cfg.top_k * cfg.moe_d_ff + cfg.n_shared_experts * cfg.moe_d_ff
         if cfg.n_experts else cfg.d_ff)

    if shape.kind == "train":
        T = B_loc * shape.seq_len
        w = 3 * P_tp + (1 + 4 * 4 / pb) * P_dev * 2
        acts = L * T * ab * (10 * D + 6 * F / max(mp, 1))
        logits = 4 * T * (cfg.vocab / mp) * 4
        return w + acts + logits
    if shape.kind == "prefill":
        T = B_loc * shape.seq_len
        w = P_tp
        acts = L * T * ab * (6 * D + 3 * F / max(mp, 1))
        cache = _cache_bytes(lm, shape, devices)
        return w + acts + cache
    # decode: weights read once per step (batch>1 touches ~all experts) +
    # the whole resident cache. Experts shard over the full mesh at serve
    # time when divisible (SERVE_RULES; §Perf iteration 2).
    del n_active
    if cfg.n_experts:
        moe_layers = sum(1 for d in lm.descs if d.mlp == "moe")
        expert_params = moe_layers * cfg.n_experts * 3 * cfg.d_model * cfg.moe_d_ff
        ep = devices if cfg.n_experts % devices == 0 else mp
        w = (n_params - expert_params) * pb / mp + expert_params * pb / ep
    else:
        w = P_tp
    return w + _cache_bytes(lm, shape, devices)


def _cache_bytes(lm: LM, shape, devices: int) -> float:
    n_front = (lm.cfg.n_frontend_tokens
               if lm.cfg.frontend == "vision_stub" else 0)
    enc_len = shape.seq_len if lm.cfg.n_enc_layers else 0
    metas = lm.decode_cache_meta(shape.global_batch, shape.seq_len + n_front,
                                 enc_len)
    total = 0
    for seg in metas:
        for s in jax.tree.leaves(seg):
            total += int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
    return total / devices


def model_flops(cfg, lm: LM, shape, devices: int) -> float:
    """Per-device MODEL_FLOPS: 6·N·D for training, 2·N_active·D for serving."""
    n_active = lm.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / devices
    tokens = shape.global_batch  # one token per sequence per step
    return 2.0 * n_active * tokens / devices


def analyze_cell(arch: str, shape_name: str, artifact_dir: str,
                 out_dir: str, force: bool = False):
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    cell_path = os.path.join(artifact_dir, f"{arch}__{shape_name}__single_pod.json")
    if not os.path.exists(cell_path):
        return None
    with open(cell_path) as f:
        cell = json.load(f)
    if cell["status"] != "ok":
        rec = {"arch": arch, "shape": shape_name, "status": cell["status"],
               "reason": cell.get("reason", cell.get("error", ""))}
        _write(out_path, rec)
        return rec

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    devices = int(np.prod(list(mesh.shape.values())))
    lm = LM(cfg)
    R = [s.repeats for s in lm.segments]

    t0 = time.time()
    base = _measure(cfg, shape_name, mesh, [1] * len(R))
    units = []
    for k in range(len(R)):
        if R[k] == 1:
            units.append({k2: 0.0 for k2 in base})
            continue
        reps = [1] * len(R)
        reps[k] = 2
        units.append(_sub(_measure(cfg, shape_name, mesh, reps), base))

    full = {"flops": cell["flops_per_device"],
            "bytes": cell["bytes_per_device"],
            "coll": float(sum(cell["collective_bytes"].values())),
            "wire": float(sum(cell["collective_wire_bytes"].values()))}
    corr = dict(full)
    for k, u in enumerate(units):
        for key in corr:
            corr[key] += (R[k] - 1) * u[key]

    mf = model_flops(cfg, lm, shape, devices)
    pk = peaks(V5E)
    terms = {
        "compute_s": corr["flops"] / pk["flops"],
        "memory_hlo_s": corr["bytes"] / pk["hbm_bw"],  # unfused upper bound
        "memory_s": analytic_memory_bytes(cfg, lm, shape,
                                          dict(mesh.shape)) / pk["hbm_bw"],
        "collective_s": corr["coll"] / pk["link_bw"],
        "collective_wire_s": corr["wire"] / pk["link_bw"],
    }
    core = {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")}
    dominant = max(core, key=core.get)
    bound = max(core.values())
    rec = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "kind": cell["kind"], "devices": devices,
        "hlo": full, "corrected": corr, "segment_repeats": R,
        "model_flops_per_device": mf,
        "useful_ratio": mf / corr["flops"] if corr["flops"] else None,
        "terms": terms,
        "dominant": dominant,
        "roofline_fraction": (terms["compute_s"] / bound) if bound else None,
        "analysis_s": round(time.time() - t0, 1),
    }
    _write(out_path, rec)
    print(f"[roofline] {arch:24s} {shape_name:12s} dominant={dominant:12s} "
          f"compute={terms['compute_s']*1e3:9.2f}ms memory={terms['memory_s']*1e3:9.2f}ms "
          f"coll={terms['collective_s']*1e3:9.2f}ms useful={rec['useful_ratio']:.3f}")
    return rec


def _write(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def emit_markdown(out_dir: str) -> str:
    rows = []
    for a in ARCH_NAMES:
        for s in ALL_SHAPES:
            p = os.path.join(out_dir, f"{a}__{s.name}.json")
            if os.path.exists(p):
                with open(p) as f:
                    rows.append(json.load(f))
    lines = ["| arch | shape | dominant | compute (ms) | memory (ms) | "
             "mem-HLO-ub (ms) | collective (ms) | MODEL/HLO flops | "
             "roofline frac |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — skipped: "
                         f"{r.get('reason','')[:60]} | | | | | | |")
            continue
        t = r["terms"]
        mh = t.get("memory_hlo_s", t["memory_s"])
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['dominant'].replace('_s','')} "
            f"| {t['compute_s']*1e3:.2f} | {t['memory_s']*1e3:.2f} "
            f"| {mh*1e3:.2f} "
            f"| {t['collective_s']*1e3:.2f} | {r['useful_ratio']:.3f} "
            f"| {r['roofline_fraction']:.3f} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--artifacts", default=ARTIFACT_DIR)
    ap.add_argument("--out", default=ROOF_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    if args.markdown:
        print(emit_markdown(args.out))
        return
    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    shapes = [args.shape] if args.shape else [s.name for s in ALL_SHAPES]
    for a in archs:
        for s in shapes:
            try:
                analyze_cell(a, s, args.artifacts, args.out, force=args.force)
            except Exception as e:  # noqa: BLE001
                print(f"[roofline-ERROR] {a} {s}: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
