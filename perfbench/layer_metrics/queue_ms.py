"""L5 serving: median of the program's ``queue`` spans in the window (from
``submit()`` to joining a slot, host clock)."""

from perfbench.harness.stats import median


def read(obs):
    return median([(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in obs.spans
                   if s["name"] == "queue"])
