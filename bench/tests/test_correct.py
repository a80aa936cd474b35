"""The comparison that decides ``correct``, driven through a whole run
at a size the CPU holds: a sound run passes, the bfloat16 control in
the planner's place does not, and each fault planted in the timed path
(an answer or a planner score altered where it is produced, a sampler
that ignores the planner, a query never answered) makes ``correct``
false.  A fault in the build's signing shows in ``sig_wrong``.  The
look for a chip is skipped."""
import json
import os
import time

import numpy as np
import pytest

from bench import deploy, faults, run
from bench.reference import Reference, _pack_signs

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**33 + 17


def _tiny():
    """The wiki-phrase deployment and open-loop traffic cut to a size the
    CPU runs in seconds, with every query kind in the mix."""
    with open(os.path.join(run.ROOT, "bench", "configs",
                           "wiki-phrase.json")) as f:
        cfg = json.load(f)
    cfg["corpus"].update(n_docs=1500, vocab_size=1024)
    cfg["shard_tokens"] = 2048
    cfg["pv_dbow"].update(dim=16, steps=200, batch_pairs=512)
    with open(os.path.join(run.ROOT, "bench", "traffic",
                           "phrase-open.json")) as f:
        traffic = json.load(f)
    traffic.update(rate_qps=24.0, warmup_queries=8,
                   mix={"count": 0.6, "bool": 0.2, "ranked": 0.2},
                   bool_forms=["and", "or-and"], ranked_words=[3, 3],
                   ranked_k=10, check={"count": 24, "bool": 12, "ranked": 12})
    return cfg, traffic


def _run(hook=None, control=False):
    cfg, traffic = _tiny()
    return run.run_cell(cfg, traffic, SEED, 2.0, False, require_chip=False,
                        control=control, hook=hook,
                        e2e=[dict(name="p50_ms", unit="ms")],
                        t_start=time.perf_counter())


def test_sound_run_is_correct_and_control_is_not():
    out = _run(control=True)
    sound, c = out["program"], out["checks"]
    print(json.dumps(sound), json.dumps(c))
    assert sound["correct"], sound
    assert list(out)[-1] == "checks"
    s = sound["checks"]
    assert s["plan_gap"]["value"] < s["plan_gap"]["limit"] / 10
    assert s["sig_wrong"]["value"] == 0
    assert not out["correct"]
    assert c["plan_gap"]["value"] > c["plan_gap"]["limit"]


@pytest.mark.parametrize("fault,number", [
    ("count", "count_wrong"),
    ("bool", "bool_wrong"),
    ("ranked", "ranked_wrong"),
    ("plan", "plan_gap"),
    ("uniform", "draw_z"),
    ("unanswered", "unanswered"),
])
def test_a_planted_fault_makes_the_run_incorrect(fault, number, monkeypatch):
    monkeypatch.setattr(run, "SETTLE_S", 1.0)
    out = _run(hook=faults.FAULTS[fault])
    print(json.dumps(out["checks"]))
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


def test_a_fault_in_signing_shows_in_sig_wrong():
    cfg, _ = _tiny()
    dep = deploy.build(cfg, deploy.sub_seeds(cfg["data_seed"]), 1)
    try:
        t = dep.tables
        assert Reference(dep.corpus, dep.shard_docs, t).index()[
            "sig_wrong"] == 0
        import jax
        planes = np.asarray(jax.random.normal(
            jax.random.PRNGKey(t["lsh_seed"]),
            (t["bits"], t["doc_vecs"].shape[1])))
        # the centring left out of the documents' signatures
        raw = dict(t, doc_sig=_pack_signs(t["doc_vecs"] @ planes.T >= 0))
        assert Reference(dep.corpus, dep.shard_docs, raw).index()[
            "sig_wrong"] > 0
        # each shard signed by its first document, not by the mean
        ix = Reference(dep.corpus, dep.shard_docs, t).index()
        first = np.stack([ix["doc_sig"][d[0]] for d in dep.shard_docs])
        one = dict(t, shard_sig=first)
        assert Reference(dep.corpus, dep.shard_docs, one).index()[
            "sig_wrong"] > 0
    finally:
        dep.close()
