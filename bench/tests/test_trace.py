"""The trace reduction, on a small trace recorded on a TPU v5e: six
calls of the fused asym segment-sum kernel (batches of 1, 4, 8, twice),
each under a ``bench.plan`` annotation and followed by 20 ms of host
work under ``bench.scan``."""
import gzip
import os
import shutil

import pytest

from bench import roofline
from bench.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "segsum.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "segsum.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return Trace(str(path))


def test_the_kernel_is_found_by_name(trace):
    n, t = trace.kernel(roofline.KERNELS["segsum"])
    assert n == 6
    assert 0 < t < 1.0
    assert trace.top_ops(1)[0][0].startswith("asym_segment_sum_kernel")


def test_busy_time_is_the_union_of_op_intervals(trace):
    a, b = trace.span()
    busy = trace.busy_s()
    n, t = trace.kernel(roofline.KERNELS["segsum"])
    assert t <= busy < b - a
    assert list(trace.devices) == ["/device:TPU:0"]


def test_idle_gaps_are_put_down_to_the_host_span_over_them(trace):
    a, b = trace.span()
    gaps = trace.idle_gaps(a, b, 5)
    assert len(gaps) == 5
    assert all(g[1] > 0 for g in gaps)
    # the 20 ms sleeps under bench.scan are the longest gaps
    assert [g[0] for g in gaps[:4]] == ["bench.scan"] * 4
    assert gaps[0][1] >= 0.019


def test_the_roofline_share_of_the_recorded_calls_is_below_100(trace):
    peak = roofline.peaks("TPU v5 lite")
    least = sum(roofline.min_time(*roofline.segsum_cost(
        b, 200000, bits=256, dim=100, segments=1940), peak)[0]
        for b in (1, 4, 8, 1, 4, 8))
    share = 100 * least / trace.kernel(roofline.KERNELS["segsum"])[1]
    assert 0 < share < 100


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
