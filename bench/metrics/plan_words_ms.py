"""Planning: mean milliseconds per batch in the Boolean word scoring
and its AND/OR algebra (the engine's ``emapprox.plan.words`` span, its
device wait left out), from the window's ``plan_words_us`` counter;
None where the program does not keep it."""


def read(obs):
    s = obs.stats
    if "plan_words_us" not in s or not s.get("batches"):
        return None
    return s["plan_words_us"] / s["batches"] / 1e3
