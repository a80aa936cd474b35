"""The per-layer readers of the retrieval cell's counters and kernel:
each on a hand-built diff of ``BatchWindow.stats``, with nothing to
divide by, on a program without the counters, and the asym similarity
kernel's roofline share on a synthetic device trace."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import observe, roofline  # noqa: E402
from bench.trace import Trace  # noqa: E402

STATS = dict(batches=20, served=50, plan_words_us=60_000,
             reduce_topk_us=140_000, topk_candidates=1_500_000,
             topk_queries=30, bool_words=180, bool_plans=12,
             plan_shards=40_000, group_pairs=30_000, segsum_calls=14,
             segsum_lanes=14 * 256)

READERS = {  # metric -> (value on STATS, counter whose denominator is 0)
    "plan_words_ms.retrieval": (3.0, "batches"),
    "topk_ms.retrieval": (7.0, "batches"),
    "topk_cands.retrieval": (50_000.0, "topk_queries"),
    "group_share.retrieval": (0.75, "plan_shards"),
    "segsum_lanes.retrieval": (256.0, "segsum_calls"),
}
SIZES = dict(n_docs=200_000, n_shards=1939, bits=256, dim=100)


def _obs(stats, trace=None):
    return SimpleNamespace(stats=stats, trace=trace, sizes=SIZES,
                           device_kind="TPU v5 lite")


def _trace(events):
    """A ``Trace`` over one TPU plane holding ``events`` ((name, start_s,
    end_s) each), as the profiler's reduction would leave it."""
    tr = Trace.__new__(Trace)
    tr.devices = {"/device:TPU:0": list(events)}
    tr.host = []
    return tr


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_stats_diff(name):
    want, _ = READERS[name]
    assert observe.reader(name)(_obs(dict(STATS))) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS) +
                         ["wordsim_roofline_pct.retrieval"])
def test_reader_gives_none_with_nothing_to_divide_by(name):
    den = READERS.get(name, (None, "bool_plans"))[1]
    trace = _trace([("asym_similarity_kernel.1", 0.0, 2e-5)])
    assert observe.reader(name)(_obs(dict(STATS, **{den: 0}), trace)) is None


@pytest.mark.parametrize("name", sorted(READERS) +
                         ["wordsim_roofline_pct.retrieval"])
def test_reader_gives_none_without_the_counters(name):
    """A program that keeps only the counters of the phrase cells (the
    retrieval counters are newer) reads nothing and raises nothing."""
    old = dict(batches=20, served=50, queue_hol_us=9_000, plan_wait_us=4_000,
               reduce_us=5_000, scan_us=60_000, shard_visits=40)
    trace = _trace([("asym_similarity_kernel.1", 0.0, 2e-5)])
    assert observe.reader(name)(_obs(old, trace)) is None


def test_segsum_lanes_reads_the_same_mean_in_both_open_loop_cells():
    """One reader serves ``segsum_lanes.open`` and ``.retrieval``: the
    mean band width a fused planning call contracted."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {m["name"]: m["workloads"] for m in bench["per_layer"]
             if m["name"].startswith("segsum_lanes.")}
    assert cells == {"segsum_lanes.open": ["wiki-phrase.steady"],
                     "segsum_lanes.retrieval": ["wiki-retrieval.steady"]}
    stats = dict(batches=9, served=12, segsum_calls=8,
                 segsum_lanes=3 * 128 + 5 * 256)
    for name in cells:
        assert observe.reader(name)(_obs(stats)) == pytest.approx(208.0)


def test_wordsim_cost_counts_the_algorithm():
    read = observe.reader("wordsim_roofline_pct.retrieval")
    cost = read.__globals__["wordsim_cost"]
    flops, nbytes = cost(10, 1939, bits=256, dim=100)
    assert flops == 2 * 10 * 256 * 100 + 2 * 10 * 1939 * 256 + 10 * 1939
    assert nbytes == (1939 * 256 / 8 + 4 * 10 * 1939 + 4 * 10 * 100
                      + 4 * 256 * 100)


def test_wordsim_roofline_on_a_synthetic_trace():
    """Twelve 20 us calls of the similarity kernel beside segment-sum
    calls that it must not count: a share above 0 and at most 100, the
    least time of the mean call (15 words) times the calls."""
    read = observe.reader("wordsim_roofline_pct.retrieval")
    events = []
    for i in range(12):
        t = i * 1e-3
        events.append(("asym_similarity_kernel.1", t, t + 2e-5))
        events.append(("asym_segment_sum_kernel.1", t + 5e-4, t + 9e-4))
    trace = _trace(events)
    share = read(_obs(dict(STATS), trace))
    assert 0.0 < share <= 100.0
    cost = read.__globals__["wordsim_cost"](
        15.0, 1939, bits=256, dim=100)
    least, bound = roofline.min_time(*cost, roofline.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert share == pytest.approx(100.0 * 12 * least / (12 * 2e-5))
    # a trace without the kernel, or no trace, reads nothing
    no_calls = _trace([e for e in events if "segment" in e[0]])
    assert read(_obs(dict(STATS), no_calls)) is None
    assert read(_obs(dict(STATS))) is None


def test_every_retrieval_metric_in_the_benchmark_has_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".retrieval")]
    assert set(READERS) | {"wordsim_roofline_pct.retrieval"} <= {
        m["name"] for m in mine}
    for m in mine:
        assert m["workloads"] == ["wiki-retrieval.steady"]
        assert m["moves"] == "p50_ms"
        read = observe.reader(m["name"])
        assert read.__module__.endswith(m["name"].split(".")[0])
