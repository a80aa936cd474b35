"""Front end: mean milliseconds from a query's due time to the entry of
the ``QueryBatch.execute`` that serves it, over the queries due in the
window (host clock)."""
import numpy as np


def read(obs):
    entry = {}
    for b in obs.layers.batches:
        for qid in b["ids"]:
            entry.setdefault(qid, b["t"])
    o = obs.offered
    waits = [entry[id(o.queries[i])] - o.due[i] for i in obs.due_in_window()
             if id(o.queries[i]) in entry]
    return 1e3 * float(np.mean(waits)) if waits else None
