"""Executor: mean host milliseconds per batch in
``ShardTaskExecutor.map_shard_batch``, the shared scan of the sampled
shards, over the batches that entered in the window."""
import numpy as np


def read(obs):
    b = obs.batches()
    return 1e3 * float(np.mean([x["scan_s"] for x in b])) if b else None
