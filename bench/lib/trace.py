"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is held as plain intervals in the profiler's own clock
(nanoseconds): for each device plane, for each line, ``(name, start, end)``;
and the benchmark's host annotations (``TraceAnnotation`` names that start
with ``bench.``), which the profiler writes on the same clock. ``load``
reads a profile directory written by ``jax.profiler``; ``to_json`` /
``from_json`` keep a small trace as a test fixture.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PREFIX = "bench."
# lines of a device plane, finest first: what an operation running looks like
OP_LINES = ("XLA Ops", "XLA Modules")
MODULE_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    device: Dict[str, Dict[str, List[Interval]]]
    host: List[Interval]

    def annotation(self, name: str) -> Optional[Tuple[float, float]]:
        """(start, end) of the first host annotation called ``name``."""
        for n, s, e in self.host:
            if n == name:
                return s, e
        return None

    def op_intervals(self, plane: str) -> List[Tuple[float, float]]:
        lines = self.device[plane]
        for ln in OP_LINES:
            if lines.get(ln):
                return [(s, e) for _, s, e in lines[ln]]
        return []

    def to_json(self) -> dict:
        return {"device": self.device, "host": self.host}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        dev = {p: {ln: [tuple(ev) for ev in evs] for ln, evs in lines.items()}
               for p, lines in obj["device"].items()}
        return cls(dev, [tuple(ev) for ev in obj["host"]])


def profile_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(profile_file(log_dir))
    device: Dict[str, Dict[str, List[Interval]]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in OP_LINES:
                    lines[line.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                        for ev in line.events]
            device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    host.sort(key=lambda iv: iv[1])
    return Trace(device, host)


def clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Disjoint, sorted cover of the given intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace, plane: str, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran on ``plane``."""
    return sum(e - s for s, e in union(clip(trace.op_intervals(plane), lo,
                                            hi)))


def idle_gaps(trace: Trace, plane: str, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] in which nothing ran on ``plane``."""
    gaps, t = [], lo
    for s, e in union(clip(trace.op_intervals(plane), lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def matched_ns(trace: Trace, plane: str, line: str, pattern: str, lo: float,
               hi: float) -> float:
    """Summed duration inside [lo, hi] of ``line`` events whose name
    matches the regular expression ``pattern``."""
    rx = re.compile(pattern)
    evs = trace.device.get(plane, {}).get(line, [])
    return sum(e - s for _, s, e in
               ((n, max(s, lo), min(e, hi)) for n, s, e in evs
                if rx.search(n)) if e > s)


def top_ops(trace: Trace, planes: List[str], lo: float, hi: float,
            n: int = 10) -> List[List]:
    """The ``n`` device operations that took most time, by name, in
    seconds summed over the planes."""
    tot: Dict[str, float] = {}
    for p in planes:
        lines = trace.device[p]
        line = next((ln for ln in OP_LINES if lines.get(ln)), None)
        for name, s, e in lines.get(line, []):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_timeline(trace: Trace) -> List[Interval]:
    """Disjoint ``(name, start, end)`` pieces of the host annotations, each
    piece named after the innermost (shortest) annotation covering it."""
    edges = []
    for i, (name, s, e) in enumerate(trace.host):
        if name != "bench.window" and e > s:
            edges += [(s, 1, i), (e, 0, i)]
    edges.sort()
    active = set()
    out: List[Interval] = []
    t_prev = None
    for t, opening, i in edges:
        if active and t_prev is not None and t > t_prev:
            inner = min(active, key=lambda j: trace.host[j][2]
                        - trace.host[j][1])
            out.append((trace.host[inner][0], t_prev, t))
        if opening:
            active.add(i)
        else:
            active.discard(i)
        t_prev = t
    return out


def gaps_by_host(trace: Trace, planes: List[str], lo: float, hi: float,
                 n: int = 10) -> List[List]:
    """Idle device time attributed to what the host was doing: each part
    of a gap goes to the innermost ``bench.`` annotation covering it
    (``host.none`` where none does), in seconds summed over the planes."""
    import bisect
    timeline = host_timeline(trace)
    starts = [s for _, s, _ in timeline]
    tot: Dict[str, float] = {}

    def add(name, ns):
        tot[name] = tot.get(name, 0.0) + ns * 1e-9

    for p in planes:
        for gs, ge in idle_gaps(trace, p, lo, hi):
            covered = 0.0
            i = max(bisect.bisect_right(starts, gs) - 1, 0)
            while i < len(timeline) and timeline[i][1] < ge:
                name, s, e = timeline[i]
                a, b = max(s, gs), min(e, ge)
                if b > a:
                    add(name, b - a)
                    covered += b - a
                i += 1
            if ge - gs > covered:
                add("host.none", ge - gs - covered)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def excerpt(trace: Trace, lo: float, hi: float) -> Trace:
    """The events of ``trace`` that overlap [lo, hi], unclipped."""
    dev = {p: {ln: [ev for ev in evs if ev[2] > lo and ev[1] < hi]
               for ln, evs in lines.items()}
           for p, lines in trace.device.items()}
    return Trace(dev, [ev for ev in trace.host if ev[2] > lo and ev[1] < hi])


def summary(trace: Trace) -> dict:
    """Per device line: event count, summed seconds and the ten names that
    took most time; and the host annotations' counts."""
    out = {}
    for p, lines in trace.device.items():
        for ln, evs in lines.items():
            tot: Dict[str, float] = {}
            for name, s, e in evs:
                tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
            out[f"{p} {ln}"] = {"events": len(evs),
                                "seconds": sum(tot.values()), "top": top}
    names: Dict[str, int] = {}
    for name, _, _ in trace.host:
        names[name] = names.get(name, 0) + 1
    out["host"] = names
    return out
