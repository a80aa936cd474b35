"""Kernel: the fused asym segment-sum kernel's share of its roofline,
in percent: the least time the chip needs for the traced calls (the
larger of FLOPs over peak and bytes over HBM bandwidth, from each
call's shapes) over the kernel's device time in the trace."""
from bench import roofline


def read(obs):
    if obs.trace is None:
        return None
    n, t = obs.trace.kernel(roofline.KERNELS["segsum"])
    # a call made just before the trace stopped may have run on the
    # device after it: its event is missing, so the last calls go
    calls = sorted(obs.traced_calls(), key=lambda c: c[1])
    if not n or n > len(calls):
        return None
    calls = calls[:n]
    peak = roofline.peaks(obs.device_kind)
    least = sum(roofline.min_time(*obs.segsum_cost(c[0]), peak)[0]
                for c in calls)
    return 100.0 * least / t
