"""Front end: the 75th percentile of the sojourn, from due time to
answer, over the queries due in the window that were answered (host
clock): the tail of ``p50_ms``'s set of queries."""
import numpy as np


def read(obs):
    o = obs.offered
    soj = (o.done - o.due)[obs.due_in_window()]
    soj = soj[np.isfinite(soj)]
    return 1e3 * float(np.percentile(soj, 75)) if len(soj) else None
