"""The steps of one run of a cell, shared by ``bench/run.py`` and
``bench/control.py``."""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path

import numpy as np

from . import data, drive, reference, spec, traffic, warm

CHECK_SAMPLE = 512


class Unfit(RuntimeError):
    """This machine cannot run the cell."""


def setup_jax(root: Path = spec.ROOT):
    """JAX with its persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every
    program however quickly it compiled."""
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        raise Unfit(f"the program is not in this checkout: {e}") from None
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def devices_for(jax, cell: spec.Cell, rehearse: bool):
    devices = jax.devices()
    if not rehearse:
        if devices[0].platform != "tpu":
            raise Unfit(f"no TPU: JAX finds {devices[0].platform}")
        if len(devices) < cell.chips:
            raise Unfit(f"cell asks for {cell.chips} chips, JAX finds "
                        f"{len(devices)}")
    return devices[:cell.chips]


@dataclasses.dataclass
class State:
    cfg: dict
    mix: dict
    corpus: data.Corpus
    requests: traffic.Requests
    system: object
    n_warm: int


def prepare(cell: spec.Cell, seed: int, seconds: float, devices,
            rehearse: int = 0) -> State:
    """Corpus, traffic and system from the seed, and the warm-up."""
    cfg = dict(cell.config)
    if rehearse:
        cfg["n"] = rehearse
    mix = cell.traffic
    corpus = data.make_corpus(cfg, seed, traffic.request_count(mix, seconds))
    requests = traffic.make_requests(mix, corpus, seed, seconds)
    system = spec.system_class(cfg)(cfg, corpus, mix["k"], devices)
    n_warm = warm.warm(system, requests, corpus.queries, mix)
    return State(cfg, mix, corpus, requests, system, n_warm)


def serve(st: State, seconds: float, mark=None) -> drive.Window:
    if st.mix["loop"] == "closed":
        return drive.closed_loop(st.system, st.requests, st.corpus.queries,
                                 seconds, st.mix["outstanding"], mark=mark)
    return drive.open_loop(st.system, st.requests, st.corpus.queries,
                           seconds, mark=mark)


@dataclasses.dataclass
class Sample:
    sends: np.ndarray       # sends checked
    request: np.ndarray     # their pool indices
    ids: np.ndarray         # (S, k) served
    dists: np.ndarray
    unanswered: int
    rejected: int


def sample(win: drive.Window, st: State, seed: int) -> Sample:
    """The answered sends to check, drawn from the seed (see
    ``reference.sample``), with what the server returned for them."""
    k = st.mix["k"]
    sends = win.sends_in_window()
    answered = np.array([s for s in sends if s in win.ids], np.int64)
    req = st.requests
    chosen = reference.sample(data.host_rng(seed, 3), answered,
                              req.count[win.request], req.mask[win.request],
                              CHECK_SAMPLE)
    ids = (np.stack([win.ids[s] for s in chosen]) if chosen.size
           else np.zeros((0, k), np.int64))
    dists = (np.stack([win.dists[s] for s in chosen]) if chosen.size
             else np.zeros((0, k)))
    return Sample(chosen, win.request[chosen], ids, dists,
                  int(np.sum(~np.isfinite(win.answered[sends]))),
                  int(np.sum(win.rejected[sends])))


def judge(st: State, smp: Sample, ref=None) -> dict:
    """The compared numbers for the sample, against the reference (computed
    here unless given as ``(ids, dists)``)."""
    if ref is None:
        ref = reference.control_or_reference(st.corpus, smp.request,
                                             st.requests, st.mix["k"], False)
    return reference.compare(st.corpus, st.requests, smp.request, smp.ids,
                             smp.dists, smp.unanswered, *ref)


def verdict(numbers: dict, smp: Sample, limits: dict) -> bool:
    return bool(smp.sends.size) and reference.verdict(numbers, limits)
