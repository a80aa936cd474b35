"""Front end: mean queries per executed batch over the window, from the
window's own ``served`` and ``batches`` counters."""


def read(obs):
    n = obs.stats.get("batches", 0)
    return obs.stats["served"] / n if n else None
