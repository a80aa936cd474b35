"""Planning: mean host milliseconds per batch in
``ApproxIndex.shard_similarities_batch`` and
``word_shard_similarities_batch``, the wait for the device included,
over the batches that entered in the window."""
import numpy as np


def read(obs):
    b = obs.batches()
    return 1e3 * float(np.mean([x["plan_s"] for x in b])) if b else None
