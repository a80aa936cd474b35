"""Planning: mean milliseconds per batch that the planner blocked on
the device for its scores (the index's ``emapprox.plan.wait`` span),
from the window's ``plan_wait_us`` counter; None where the program does
not keep it."""


def read(obs):
    s = obs.stats
    if "plan_wait_us" not in s or not s.get("batches"):
        return None
    return s["plan_wait_us"] / s["batches"] / 1e3
