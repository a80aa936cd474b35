"""Front end: mean milliseconds per served query spent waiting while
the window's dispatcher executed earlier batches (head-of-line), from
the window's ``queue_hol_us`` counter; None where the program does not
keep it."""


def read(obs):
    s = obs.stats
    if "queue_hol_us" not in s or not s.get("served"):
        return None
    return s["queue_hol_us"] / s["served"] / 1e3
