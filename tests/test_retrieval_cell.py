"""The ``wiki-retrieval.steady`` cell (Boolean and BM25 top-k at
sampling rate 0.25) driven through a whole benchmark run at a size the
CPU holds: the sound run is ``correct`` with every Boolean and ranked
answer equal to the reference's over the same draws, and a fault
planted in the group scan's per-shard results (one Boolean hit
dropped, one BM25 score off by one ulp) makes the run incorrect.  The
look for a chip is skipped."""
import json
import os
import sys
import time
from collections import Counter

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

SEED = 2**33 + 29


def _tiny():
    """The wiki-retrieval deployment and its traffic, cut as
    ``bench/tests/test_correct.py`` cuts wiki-phrase; the rate, the mix
    and the query shapes are the cell's own."""
    def load(*path):
        with open(os.path.join(ROOT, "bench", *path)) as f:
            return json.load(f)
    cfg = load("configs", "wiki-retrieval.json")
    cfg["corpus"].update(n_docs=1500, vocab_size=1024)
    cfg["shard_tokens"] = 2048
    cfg["pv_dbow"].update(dim=16, steps=200, batch_pairs=512)
    traffic = load("traffic", "retrieval-open.json")
    traffic.update(rate_qps=24.0, warmup_queries=8)
    return cfg, traffic


def _run(hook, monkeypatch):
    # the persistent compile cache stays off in the test process
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(run, "SETTLE_S", 5.0)
    cfg, traffic = _tiny()
    assert cfg["serve"]["sampling_rate"] == 0.25
    return run.run_cell(cfg, traffic, SEED, 2.0, False, require_chip=False,
                        hook=hook, e2e=[dict(name="p50_ms", unit="ms")],
                        t_start=time.perf_counter())


def _in_shard_tasks(kind, alter):
    """A hook: in every batch, each ``kind`` query's per-shard results
    from the group scan go through ``alter(result, state)``, in shard
    order, where ``state`` is the query's own dict for the batch."""
    def hook(stack):
        engine = stack.engine
        orig = type(engine)._word_scan

        def word_scan(*a):
            scan = orig(*a)
            run = scan.run_group
            states = {}

            def run_group(shard_ids, queries_of):
                out = run(shard_ids, queries_of)
                for sid in sorted(out):
                    for qi, res in out[sid].items():
                        if scan.queries[qi].kind == kind:
                            out[sid][qi] = alter(
                                res, states.setdefault(qi, {}))
                return out
            scan.run_group = run_group
            return scan
        engine._word_scan = word_scan
    return hook


def _drop_one_hit(hits, state):
    """The first shard with a hit loses its last one."""
    if len(hits) and not state:
        state["dropped"] = True
        return hits[:-1]
    return hits


def _one_ulp_up(result, state):
    """The shard's highest BM25 score one ulp higher, so the top of
    the merged ranking is off by one ulp."""
    ids, scores = result
    if not len(scores):
        return result
    scores = np.array(scores, np.float64)
    j = int(np.argmax(scores))
    scores[j] = np.nextafter(scores[j], np.inf)
    return ids, scores


def test_sound_retrieval_run_is_correct(monkeypatch):
    kinds = Counter()

    def count_answers(stack):
        engine = stack.engine
        orig = engine.execute

        def execute(queries, *a, **kw):
            out = orig(queries, *a, **kw)
            kinds.update(q.kind for q in queries)
            return out
        engine.execute = execute

    out = _run(count_answers, monkeypatch)
    c = out["checks"]
    print(json.dumps(c), dict(kinds))
    assert out["correct"], c
    assert c["bool_wrong"]["value"] == c["ranked_wrong"]["value"] == 0
    assert c["unanswered"]["value"] == 0 and out["failed"] == 0
    assert kinds["bool"] >= 1 and kinds["ranked"] >= 1
    assert kinds["count"] == 0


@pytest.mark.parametrize("kind,alter,number", [
    ("bool", _drop_one_hit, "bool_wrong"),
    ("ranked", _one_ulp_up, "ranked_wrong"),
])
def test_a_fault_in_a_shard_task_makes_the_run_incorrect(kind, alter, number,
                                                        monkeypatch):
    out = _run(_in_shard_tasks(kind, alter), monkeypatch)
    c = out["checks"]
    print(json.dumps(c))
    assert not out["correct"]
    assert c[number]["value"] > c[number]["limit"], c
