"""The per-layer readers of the program's own counters
(``BatchWindow.stats`` diffed over the window): each on a hand-built
diff, with nothing to divide by, on a program without the counters, and
found by ``observe.reader`` under its metric names."""
from types import SimpleNamespace

import pytest

from bench import observe

STATS = dict(batches=20, served=50, queue_hol_us=900_000,
             queue_delay_us=100_000, sample_us=30_000, reduce_us=50_000,
             plan_wait_us=40_000, scan_us=600_000, scan_cpu_us=720_000,
             shard_visits=400, plan_shards=1_800)

READERS = {  # metric -> (value on STATS, counter whose denominator is 0)
    "hol_ms.open": (18.0, "served"),
    "delay_ms.open": (2.0, "served"),
    "sample_ms.open": (1.5, "batches"),
    "sample_ms.closed": (1.5, "batches"),
    "reduce_ms.open": (2.5, "batches"),
    "reduce_ms.closed": (2.5, "batches"),
    "plan_wait_ms.open": (2.0, "batches"),
    "plan_wait_ms.closed": (2.0, "batches"),
    "scan_share.closed": (4.5, "shard_visits"),
    "scan_cpu_pct.open": (120.0, "scan_us"),
    "scan_cpu_pct.closed": (120.0, "scan_us"),
}


def _obs(stats):
    return SimpleNamespace(stats=stats)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_stats_diff(name):
    want, _ = READERS[name]
    assert observe.reader(name)(_obs(dict(STATS))) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_with_nothing_to_divide_by(name):
    _, den = READERS[name]
    assert observe.reader(name)(_obs(dict(STATS, **{den: 0}))) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_without_the_counters(name):
    """A program that keeps only the window's batch counts (the
    counters are newer) reads nothing and raises nothing."""
    old = dict(batches=20, served=50, cancelled=0, shed=0)
    assert observe.reader(name)(_obs(old)) is None


def test_every_new_metric_in_the_benchmark_has_its_reader():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(READERS) <= names
    for name in READERS:
        assert observe.reader(name).__module__.endswith(
            name.split(".")[0])
