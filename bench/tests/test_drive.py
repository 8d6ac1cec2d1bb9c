"""The client loops against a fake server: who is sent when, what counts
as answered, and that a slow close of the window (a profile being written)
does not cut the wait for the answers."""
import contextlib
import time

import numpy as np
import pytest

from bench.lib import drive


class FakeServer:
    """Answers every queued request at the next step; a step takes
    ``step_s`` of the fake clock."""

    def __init__(self, clock, step_s=0.1):
        self.clock, self.step_s = clock, step_s
        self.queue, self.ticket = [], 0

    def submit(self, vec, qlo, qhi, mask):
        self.ticket += 1
        self.queue.append(self.ticket)
        return self.ticket

    @property
    def queued(self):
        return len(self.queue)

    def step(self):
        self.clock.t += self.step_s
        done, self.queue = self.queue[:64], self.queue[64:]
        hit = type("Hit", (), {"ids": np.zeros(10, np.int64),
                               "dists": np.zeros(10)})
        return {t: type("Served", (), {"hit": hit})() for t in done}


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


def requests(R, due=None):
    return type("R", (), {"mask": np.arange(R) % 4, "qlo": np.zeros(R),
                          "qhi": np.ones(R), "vector": np.arange(R),
                          "due": np.zeros(R) if due is None else due})


def slow_mark(clock, seconds):
    @contextlib.contextmanager
    def mark(name):
        yield
        clock.t += seconds
    return mark


@pytest.fixture(autouse=True)
def short_drain(monkeypatch):
    monkeypatch.setattr(drive, "DRAIN_S", 1.0)


def test_closed_loop_keeps_outstanding_and_drains_after_a_slow_close():
    clock = Clock()
    srv = FakeServer(clock, step_s=0.125)
    win = drive.closed_loop(srv, requests(40), np.zeros((40, 2)), 1.0, 128,
                            clock=clock, mark=slow_mark(clock, 5.0))
    # 128 sent at once, 64 more after each of the 8 steps but the last
    assert len(win.request) == 128 + 64 * 7
    assert win.seconds == pytest.approx(1.0)
    assert np.isfinite(win.answered).all() and not win.rejected.any()
    assert list(win.request[:8]) == list(range(8))


def test_open_loop_sends_when_due_and_waits_after_a_slow_close():
    clock = Clock()
    srv = FakeServer(clock, step_s=0.25)
    due = np.linspace(0.0, 2.0, 30)
    win = drive.open_loop(srv, requests(30, due), np.zeros((30, 2)), 2.0,
                          clock=clock, sleep=clock.sleep,
                          mark=slow_mark(clock, 5.0))
    assert len(win.request) == 30
    assert np.isfinite(win.answered).all()
    assert (win.due == due).all()
    assert (win.late >= 0).all() and win.late.max() <= 0.25 + 1e-9
