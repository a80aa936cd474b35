"""Chip peaks and the work each planning kernel call needs.

``PEAKS`` is a copy of ``benchmarks/roofline.PEAKS``, keyed by
``jax.Device.device_kind``; a device that is not in it is an error.
The cost functions count what the algorithm needs for one call, from
its shapes: the query projection, the sign products, the exp and the
reduction, and one read of the signatures and the slot map.  They do
not count what an implementation adds on top (padding rows, a one-hot
product for the segment sum, extra passes for float32 precision), so
a kernel's share of its roofline says how far it is from the least
the chip could take.
"""
from __future__ import annotations

from typing import Dict, Tuple

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": dict(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


#: How each planning kernel's ops are named in a device trace.
KERNELS = {"segsum": r"asym_segment_sum_kernel|_asym_segsum_kernel"}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/roofline.py "
                         f"with their source") from None


def segsum_cost(b: int, m: int, *, bits: int, dim: int,
                segments: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of scoring ``b`` queries against ``m`` documents
    and summing the scores into ``segments`` shard slots."""
    flops = 2.0 * b * bits * dim + 2.0 * b * m * bits + 2.0 * b * m
    nbytes = (m * bits / 8 + 4.0 * m + 4.0 * b * dim + 4.0 * bits * dim
              + 4.0 * b * segments)
    return flops, nbytes


def min_time(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc, tm = flops / peak["flops"], nbytes / peak["hbm_bw"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
