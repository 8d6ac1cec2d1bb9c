"""The measured window: one client loop in the server's own thread.

The server is step-driven (``submit`` queues, ``step`` runs one round and
returns what it answered), so the client and the server share one thread,
as a single-process deployment of this server does. Every request's
latency runs from the moment it was due to the moment its answer came back
from ``step``: a late submission counts against the server.

A *send* is one request put to the server; a closed loop cycles through its
pool of requests, so one request of the pool may be sent more than once.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List

import numpy as np

DRAIN_S = 60.0


@dataclasses.dataclass
class Window:
    t0: float                   # window opened (perf_counter seconds)
    t1: float                   # window closed
    request: np.ndarray         # (S,) pool index of each send
    due: np.ndarray             # (S,) absolute due time
    late: np.ndarray            # (S,) submission minus due time, seconds
    answered: np.ndarray        # (S,) absolute answer time, nan if none
    rejected: np.ndarray        # (S,) bool: the server refused it
    ids: Dict[int, np.ndarray]  # send -> served ids
    dists: Dict[int, np.ndarray]
    steps: int

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def sends_in_window(self) -> np.ndarray:
        return np.flatnonzero(self.due <= self.t1)


class _Client:
    def __init__(self, system, requests, vectors, clock):
        self.system = system
        self.req = requests
        self.vectors = vectors
        self.clock = clock
        self.request: List[int] = []
        self.due: List[float] = []
        self.late: List[float] = []
        self.answered: Dict[int, float] = {}
        self.rejected: Dict[int, bool] = {}
        self.ids: Dict[int, np.ndarray] = {}
        self.dists: Dict[int, np.ndarray] = {}
        self.of_ticket: Dict[int, int] = {}
        self.steps = 0

    def send(self, r: int, due: float) -> None:
        req = self.req
        s = len(self.request)
        now = self.clock()
        self.request.append(r)
        self.due.append(due)
        self.late.append(now - due)
        out = self.system.submit(self.vectors[req.vector[r]], req.qlo[r],
                                 req.qhi[r], req.mask[r])
        if isinstance(out, int):
            self.of_ticket[out] = s
        else:
            self.rejected[s] = True
            self.answered[s] = now

    def step(self) -> int:
        out = self.system.step()
        now = self.clock()
        self.steps += 1
        for ticket, outcome in out.items():
            s = self.of_ticket.pop(ticket)
            self.answered[s] = now
            hit = getattr(outcome, "hit", None)
            if hit is None:
                self.rejected[s] = True
            else:
                self.ids[s] = np.asarray(hit.ids)
                self.dists[s] = np.asarray(hit.dists)
        return len(out)

    def drain(self, deadline: float) -> None:
        while self.of_ticket and self.clock() < deadline:
            self.step()

    def window(self, t0: float, t1: float) -> Window:
        S = len(self.request)
        answered = np.full(S, np.nan)
        rejected = np.zeros(S, bool)
        for s, t in self.answered.items():
            answered[s] = t
        for s in self.rejected:
            rejected[s] = True
        return Window(t0, t1, np.asarray(self.request, np.int64),
                      np.asarray(self.due), np.asarray(self.late), answered,
                      rejected, self.ids, self.dists, self.steps)


def _no_mark(name):
    return contextlib.nullcontext()


def closed_loop(system, requests, vectors, seconds: float, outstanding: int,
                clock=time.perf_counter, mark=None) -> Window:
    """``outstanding`` requests stay in the server: each answer sends the
    next request of the pool, cycling. The window is a whole number of
    rounds: it closes at the end of the first step that ends ``seconds``
    or more after it opened."""
    c = _Client(system, requests, vectors, clock)
    R = requests.mask.shape[0]
    with (mark or _no_mark)("bench.window"):
        t0 = clock()
        for _ in range(outstanding):
            c.send(len(c.request) % R, t0)
        while True:
            got = c.step()
            now = clock()
            if now - t0 >= seconds:
                break
            for _ in range(got):
                c.send(len(c.request) % R, now)
    # the wait starts once the window's mark is closed (a profile stops there)
    c.drain(clock() + DRAIN_S)
    return c.window(t0, now)


def open_loop(system, requests, vectors, seconds: float,
              clock=time.perf_counter, sleep=time.sleep, mark=None) -> Window:
    """Request ``i`` is sent ``requests.due[i]`` seconds after the window
    opens, whatever the server is doing: between rounds the client sends
    every request that has come due. The window closes ``seconds`` after it
    opened; answers to the requests due in it are waited for."""
    c = _Client(system, requests, vectors, clock)
    R = requests.mask.shape[0]
    window = (mark or _no_mark)("bench.window")
    window.__enter__()
    t0 = clock()
    t1 = t0 + seconds
    deadline = t1 + DRAIN_S
    due = t0 + requests.due
    i = 0
    while i < R or (c.of_ticket and clock() < deadline):
        now = clock()
        while i < R and due[i] <= now:
            c.send(i, due[i])
            i += 1
        if window is not None and now >= t1 and i == R:
            window.__exit__(None, None, None)
            window = None
            deadline = clock() + DRAIN_S
        if system.queued or c.of_ticket:
            c.step()
        elif i < R:
            sleep(max(0.0, min(due[i] - clock(), 0.005)))
    if window is not None:
        window.__exit__(None, None, None)
    return c.window(t0, t1)
