"""Engine: mean milliseconds per batch in the ranked merges, the
concatenation of every drawn shard's BM25 candidates and their stable
sort (the engine's ``emapprox.reduce.topk`` span), from the window's
``reduce_topk_us`` counter; None where the program does not keep it."""


def read(obs):
    s = obs.stats
    if "reduce_topk_us" not in s or not s.get("batches"):
        return None
    return s["reduce_topk_us"] / s["batches"] / 1e3
