"""Engine: mean candidates a ranked merge sorts, per ranked query
merged, from the window's ``topk_candidates`` and ``topk_queries``
counters (lower is better: a shard-local top-k would shrink it); None
where the program does not keep them or merged nothing."""


def read(obs):
    s = obs.stats
    if "topk_candidates" not in s or not s.get("topk_queries"):
        return None
    return s["topk_candidates"] / s["topk_queries"]
