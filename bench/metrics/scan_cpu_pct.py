"""Executor: process CPU time over wall time in the shared scan, in
percent (100 is one core busy throughout; four scan threads running
free would read near 400), from the window's ``scan_cpu_us`` and
``scan_us`` counters; None where the program does not keep them."""


def read(obs):
    s = obs.stats
    if "scan_cpu_us" not in s or not s.get("scan_us"):
        return None
    return 100.0 * s["scan_cpu_us"] / s["scan_us"]
