"""Executor: per-query shard scans served per shard visited, the sum
of the batches' per-query plans over their unions, from the window's
``plan_shards`` and ``shard_visits`` counters; None where the program
does not keep them or visited nothing."""


def read(obs):
    s = obs.stats
    if "plan_shards" not in s or not s.get("shard_visits"):
        return None
    return s["plan_shards"] / s["shard_visits"]
