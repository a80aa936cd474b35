"""Kernel: the asym similarity kernel's share of its roofline, in
percent, over its calls from the Boolean planner
(``ApproxIndex.word_shard_similarities_batch``, ``b`` words against
the ``M`` shard signatures): the least time the chip needs for the
traced calls over the kernel's device time in the trace.

The cost is affine in ``b``, so the mean call's cost is that of the
mean word count, ``bool_words`` / ``bool_plans`` over the window, and
the traced calls' cost is that times the trace's event count for the
kernel.  None without a trace, without the counters, or with no call
traced."""
from typing import Tuple

from bench import roofline

KERNEL = r"asym_similarity_kernel|_asym_sim_kernel"


def wordsim_cost(b: float, m: int, *, bits: int,
                 dim: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of scoring ``b`` query vectors against ``m``
    packed signatures: the projection onto the hyperplanes, the sign
    products, the exp, one read of the signatures and the planes and
    the write of the [b, m] scores."""
    flops = 2.0 * b * bits * dim + 2.0 * b * m * bits + b * m
    nbytes = m * bits / 8 + 4.0 * b * m + 4.0 * b * dim + 4.0 * bits * dim
    return flops, nbytes


def read(obs):
    s = obs.stats
    if obs.trace is None or not s.get("bool_plans"):
        return None
    n, t = obs.trace.kernel(KERNEL)
    if not n or t <= 0:
        return None
    z = obs.sizes
    cost = wordsim_cost(s["bool_words"] / s["bool_plans"], z["n_shards"],
                        bits=z["bits"], dim=z["dim"])
    least, _ = roofline.min_time(*cost, roofline.peaks(obs.device_kind))
    return 100.0 * n * least / t
