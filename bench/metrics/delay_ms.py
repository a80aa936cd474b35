"""Front end: mean milliseconds per served query from ``submit`` to its
batch's entry into the engine that the dispatcher was not busy with an
earlier batch (the window held open for its deadline or size), from the
window's ``queue_delay_us`` counter; None where the program does not
keep it."""


def read(obs):
    s = obs.stats
    if "queue_delay_us" not in s or not s.get("served"):
        return None
    return s["queue_delay_us"] / s["served"] / 1e3
