import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape x mesh) cell on 512 placeholder devices, print
memory_analysis()/cost_analysis(), and persist per-cell JSON artifacts
(memory, flops, bytes, per-collective byte totals) for §Roofline.

The XLA_FLAGS line above MUST precede every other import — jax locks the
device count on first init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi_pod
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""
import argparse
import json
import re
import sys
import time
import traceback

import numpy as np

import jax

from repro.configs import (ALL_SHAPES, ARCH_NAMES, SHAPES_BY_NAME, get_config,
                           supports_shape)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import ArchRunner

ARTIFACT_DIR = os.environ.get("DRYRUN_ARTIFACTS",
                              os.path.join(os.path.dirname(__file__),
                                           "..", "..", "..", "artifacts", "dryrun"))

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)"
                       r"\[([0-9,]*)\]")


def _type_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, n_devices: int = 1):
    """Per-device collective accounting from post-SPMD HLO.

    HLO prints only the RESULT type at the call site, so operand bytes are
    derived: all-gather operand = result/P, reduce-scatter operand = result*P,
    everything else operand = result (P = replica group size). ``wire`` is the
    estimated bytes a device moves on the ICI for the op (ring schedules)."""
    totals = {c: 0 for c in _COLLECTIVES}
    wire = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    opname_re = re.compile(
        r"=\s+(.*?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s.startswith("%") and not s.startswith("ROOT"):
            continue
        m = opname_re.search(s)
        if not m:
            continue
        result_sec, op, is_start = m.group(1), m.group(2), m.group(3)
        shapes = [_type_bytes(t) for t in _SHAPE_RE.finditer(result_sec)]
        if not shapes:
            continue
        # async -start ops carry (operand, result, ...) tuples: the gathered
        # result is the largest element
        rbytes = max(shapes) if is_start else sum(shapes)
        P = _group_size(s, n_devices)
        if op == "all-gather":
            operand = rbytes // max(P, 1)
            w = rbytes * (P - 1) // max(P, 1)
        elif op == "reduce-scatter":
            operand = rbytes * P
            w = rbytes * (P - 1)
        elif op == "all-reduce":
            operand = rbytes
            w = 2 * rbytes * (P - 1) // max(P, 1)
        elif op == "all-to-all":
            operand = rbytes
            w = rbytes * (P - 1) // max(P, 1)
        else:  # collective-permute / broadcast
            operand = rbytes
            w = rbytes
        totals[op] += operand
        wire[op] += w
        counts[op] += 1
    return totals, wire, counts


def run_cell(arch: str, shape_name: str, mesh_kind: str, artifact_dir: str,
             force: bool = False):
    cell_id = f"{arch}__{shape_name}__{mesh_kind}"
    out_path = os.path.join(artifact_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            prev = json.load(f)
        if prev.get("status") in ("ok", "skipped"):
            print(f"[cached ] {cell_id}: {prev['status']}")
            return prev
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = supports_shape(cfg, shape)
    rec = {"cell": cell_id, "arch": arch, "shape": shape_name,
           "mesh": mesh_kind, "kind": shape.kind}
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(out_path, rec)
        print(f"[skipped] {cell_id}: {why}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    t0 = time.time()
    try:
        runner = ArchRunner(cfg, mesh)
        bundle = runner.bundle_for(shape)
        with mesh:
            jf = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=bundle.donate)
            lowered = jf.lower(*bundle.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(compiled.memory_analysis())
        ca = compiled.cost_analysis() or {}
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
        hlo = compiled.as_text()
        colls, cwire, ccounts = collective_bytes(
            hlo, int(np.prod(list(mesh.shape.values()))))
        rec.update(
            status="ok",
            step=bundle.name,
            devices=int(np.prod(list(mesh.shape.values()))),
            mesh_shape={k: int(v) for k, v in mesh.shape.items()},
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            },
            flops_per_device=ca.get("flops") if isinstance(ca, dict) else None,
            bytes_per_device=ca.get("bytes accessed") if isinstance(ca, dict) else None,
            collective_bytes=colls,
            collective_wire_bytes=cwire,
            collective_counts=ccounts,
        )
        print(f"[ok     ] {cell_id}: lower {t_lower:.1f}s compile "
              f"{t_compile:.1f}s flops/dev {rec['flops_per_device']:.3e}")
    except Exception as e:  # noqa: BLE001 — record failures as artifacts
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[ERROR  ] {cell_id}: {type(e).__name__}: {e}")
    _write(out_path, rec)
    return rec


def _write(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_NAMES) + [None])
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in ALL_SHAPES] + [None])
    ap.add_argument("--mesh", default=None, choices=["single_pod", "multi_pod", None])
    ap.add_argument("--artifacts", default=ARTIFACT_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    shapes = [args.shape] if args.shape else [s.name for s in ALL_SHAPES]
    meshes = [args.mesh] if args.mesh else ["single_pod", "multi_pod"]

    if args.list:
        for a in archs:
            for s in shapes:
                ok, why = supports_shape(get_config(a), SHAPES_BY_NAME[s])
                print(f"{a:24s} {s:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    results = []
    for a in archs:
        for s in shapes:
            for m in meshes:
                results.append(run_cell(a, s, m, args.artifacts,
                                        force=args.force))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)} cells")
    if n_err:
        sys.exit(1)


if __name__ == "__main__":
    main()
