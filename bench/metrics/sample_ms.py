"""Engine: mean milliseconds per batch in the draws (the engine's
``emapprox.sample`` span), from the window's ``sample_us`` counter;
None where the program does not keep it."""


def read(obs):
    s = obs.stats
    if "sample_us" not in s or not s.get("batches"):
        return None
    return s["sample_us"] / s["batches"] / 1e3
