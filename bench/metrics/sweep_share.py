"""Executor: the share of executed batches whose shared scan ran as a
sweep on the calling thread (no pool hand-off), from the window's
``scan_sweep`` and ``batches`` counters; None where the program does
not keep ``scan_sweep`` or served no batch."""


def read(obs):
    s = obs.stats
    if "scan_sweep" not in s or not s.get("batches"):
        return None
    return s["scan_sweep"] / s["batches"]
