"""Executor: the share of the batches' (query, shard) pairs that the
group scan answered in one pass over the corpus-wide postings, from the
window's ``group_pairs`` and ``plan_shards`` counters; None where the
program does not keep ``group_pairs`` or planned nothing."""


def read(obs):
    s = obs.stats
    if "group_pairs" not in s or not s.get("plan_shards"):
        return None
    return s["group_pairs"] / s["plan_shards"]
