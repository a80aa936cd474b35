"""Engine: mean milliseconds per batch in the reduce, cache populate
and budget feedback (the engine's ``emapprox.reduce`` span), from the
window's ``reduce_us`` counter; None where the program does not keep
it."""


def read(obs):
    s = obs.stats
    if "reduce_us" not in s or not s.get("batches"):
        return None
    return s["reduce_us"] / s["batches"] / 1e3
