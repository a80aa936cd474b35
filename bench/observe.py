"""What a run observed, and the per-layer metric readers that read it.

A per-layer metric ``<base>.<cell suffix>`` is read by the module
``bench/metrics/<name>.py`` if there is one, else by
``bench/metrics/<base>.py``: a later PR adds a metric by adding its
reader, and a new cell reuses a reader by naming its metric after it.
A reader is ``read(obs) -> float | None``; None leaves the metric out
of the result line.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List, Optional

import numpy as np

from bench import roofline

HERE = os.path.dirname(os.path.abspath(__file__))


class Observation:
    """One run's window: the offered queries and their times, the layer
    spans, the window's counters over the window, and (with a trace)
    the reduced device trace and the kernel calls made while it ran."""

    def __init__(self, offered, layers, stats: Dict[str, int], t0: float,
                 t1: float, sizes: Dict[str, int], device_kind: str,
                 trace=None, traced: Optional[tuple] = None):
        self.offered = offered
        self.layers = layers
        self.stats = stats
        self.t0, self.t1 = t0, t1
        self.sizes = sizes
        self.device_kind = device_kind
        self.trace = trace
        self.traced = traced            # host times the trace spans

    # ---- queries and batches --------------------------------------
    def due_in_window(self) -> np.ndarray:
        o = self.offered
        due = o.due[:o.used]
        return np.nonzero((due >= self.t0) & (due < self.t1))[0]

    def batches(self) -> List[Dict[str, Any]]:
        return [b for b in self.layers.batches
                if self.t0 <= b["t"] < self.t1]

    # ---- segment-sum planner calls --------------------------------
    def traced_calls(self) -> List[tuple]:
        """(rows, host time) of the calls made while the trace ran."""
        a, b = self.traced
        return [c for c in self.layers.calls if a <= c[1] <= b]

    def segsum_cost(self, rows: int):
        s = self.sizes
        return roofline.segsum_cost(rows, s["n_docs"], bits=s["bits"],
                                    dim=s["dim"], segments=s["n_shards"])


def reader(name: str):
    """The ``read`` function for per-layer metric ``name``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                            f"under bench/metrics/")
