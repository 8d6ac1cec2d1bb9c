"""The comparison that decides ``correct``: the control (the reference at
bfloat16x3) fails it, and so does the served path with a fault planted
underneath, at a size a test run can hold."""
import dataclasses
import json

import numpy as np
import pytest

from bench import run as bench_run
from bench.lib import data, reference, spec, traffic

SIZE = 3000


@pytest.mark.parametrize("config", ["sift128-rr", "gist960-rr"])
def test_control_fails_reference_passes(config):
    cell = next(w["name"] for w in json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]
        if w["config"] == config)
    c = spec.load_cell(cell)
    cfg = dict(c.config, n=SIZE)
    corpus = data.make_corpus(cfg, 17, 256)
    req = traffic.make_requests(c.traffic, corpus, 17, 2.0)
    idx = np.arange(min(128, req.mask.shape[0]))
    k = c.traffic["k"]
    ref = reference.control_or_reference(corpus, idx, req, k, False)
    ctl = reference.control_or_reference(corpus, idx, req, k, True)
    limits = cfg["check"]
    ok = reference.compare(corpus, req, idx, ref[0], ref[1].astype(
        np.float32), 0, *ref)
    assert reference.verdict(ok, limits), ok
    bad = reference.compare(corpus, req, idx, ctl[0], ctl[1], 0, *ref)
    assert not reference.verdict(bad, limits), bad


def altered(res):
    ids = np.array(res.ids)
    ids[0, 0] = (ids[0, 0] + 1) % SIZE
    return dataclasses.replace(res, ids=ids)


def half_left_out(res):
    ids, dists = np.array(res.ids), np.array(res.dists)
    half = ids.shape[0] // 2
    ids[half:] = -1
    dists[half:] = np.inf
    return dataclasses.replace(res, ids=ids, dists=dists)


@pytest.mark.parametrize("fault", [altered, half_left_out])
def test_planted_fault_reads_incorrect(fault, monkeypatch, capsys):
    from repro.core.engine import QueryEngine
    execute = QueryEngine.execute
    monkeypatch.setattr(QueryEngine, "execute",
                        lambda self, req: fault(execute(self, req)))
    rc = bench_run.main(["--workload", "sift128-rr.wide.sat", "--seed", "5",
                         "--seconds", "1", "--rehearse", str(SIZE)])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False, res["check"]
