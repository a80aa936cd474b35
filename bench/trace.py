"""Reduction of a profiler trace to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
planes' op events give busy time (the union of their intervals), kernel
time by name and the longest idle gaps; the host plane's ``bench.*``
annotations say what the host was doing in each gap.  Everything is
derived from the trace alone, so every PR computes the same numbers in
the same way.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

OPS_LINES = ("XLA Ops",)
# one plane per chip; a TPU trace also holds planes such as
# "/device:CUSTOM:Megascale Trace" that are not chips
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def find_trace(log_dir: str) -> str:
    got = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                           recursive=True))
    if not got:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return got[-1]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Trace:
    """Device op events ([name, start_s, end_s] per device plane) and
    host annotation spans of one trace."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.devices: Dict[str, List[Tuple[str, float, float]]] = {}
        self.host: List[Tuple[str, float, float]] = []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                evs = []
                for line in plane.lines:
                    if line.name in OPS_LINES:
                        evs += [(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events]
                self.devices[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.host += [(e.name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events
                                  if e.name.startswith("bench.")]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the device planes."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for evs in self.devices.values():
            tot += sum(b - a for a, b in _union([(s, e) for _, s, e in evs]))
        return tot / len(self.devices)

    def kernel(self, pattern: str) -> Tuple[int, float]:
        """(events, seconds) of the ops whose name matches ``pattern``,
        summed over the device planes."""
        rx = re.compile(pattern)
        n, t = 0, 0.0
        for evs in self.devices.values():
            for name, s, e in evs:
                if rx.search(name):
                    n += 1
                    t += e - s
        return n, t

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` ops with the most device time, by HLO instruction
        name (an event's name is the instruction's text, shapes and
        all)."""
        tot: Dict[str, float] = {}
        for evs in self.devices.values():
            for name, s, e in evs:
                op = name.split(" = ")[0].lstrip("%")
                tot[op] = tot.get(op, 0.0) + (e - s)
        return [[n, t] for n, t in sorted(tot.items(),
                                          key=lambda x: -x[1])[:k]]

    def idle_gaps(self, start: float, end: float,
                  k: int = 10) -> List[List]:
        """The ``k`` longest device idle gaps inside [start, end] on the
        first device plane, each named by the ``bench.*`` host span that
        overlaps it most ("host idle" where none does)."""
        if not self.devices:
            return []
        evs = next(iter(self.devices.values()))
        busy = _union([(s, e) for _, s, e in evs])
        gaps, t = [], start
        for a, b in busy:
            if a > t:
                gaps.append((t, min(a, end)))
            t = max(t, b)
        if t < end:
            gaps.append((t, end))
        gaps = sorted((g for g in gaps if g[1] > g[0]),
                      key=lambda g: g[0] - g[1])[:k]
        out = []
        for a, b in gaps:
            # the innermost span wins: a plan or scan span over an
            # execute span, an execute span over nothing
            best, name = None, "between batches"
            for n, s, e in self.host:
                key = (n != "bench.execute", min(b, e) - max(a, s))
                if key[1] > 0 and (best is None or key > best):
                    best, name = key, n
            out.append([name, b - a])
        return out

    def span(self) -> Tuple[float, float]:
        """First start and last end over every event read."""
        pts = [(s, e) for evs in self.devices.values() for _, s, e in evs]
        pts += [(s, e) for _, s, e in self.host]
        if not pts:
            return 0.0, 0.0
        return min(p[0] for p in pts), max(p[1] for p in pts)

