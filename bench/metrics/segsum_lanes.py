"""Kernel: mean shard slots the fused segment-sum kernel contracted
per doc tile, per planning call that ran it, from the window's
``segsum_lanes`` and ``segsum_calls`` counters (lower is better: the
width of each tile's one-hot, all padded slots unless the kernel
contracts a band); None where the program does not keep them or ran
no such call."""


def read(obs):
    s = obs.stats
    if "segsum_lanes" not in s or not s.get("segsum_calls"):
        return None
    return s["segsum_lanes"] / s["segsum_calls"]
