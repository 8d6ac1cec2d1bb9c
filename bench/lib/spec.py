"""``BENCHMARK.json`` and the data files it names.

A cell (an entry of ``workloads``) names a configuration, whose file the
``configs`` entry gives, and a traffic mix, read from
``bench/traffic/<traffic>.json``. Its metrics are the entries of
``end_to_end`` and ``per_layer`` that list the cell under ``workloads``, or
that have no ``workloads`` key. A per-layer metric ``<base>.<split>`` is
read by ``bench/metrics/<base>.py``; the split only says which end-to-end
metric it moves.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, traffic,
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name))


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    base = name.split(".", 1)[0]
    return _module(root / "bench" / "metrics" / f"{base}.py",
                   f"bench_metric_{base}").read


def system_class(config: dict, root: Path = ROOT):
    """The ``System`` class of the configuration's ``system``."""
    name = config["system"]
    return _module(root / "bench" / "systems" / f"{name}.py",
                   f"bench_system_{name}").System

