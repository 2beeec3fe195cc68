"""L5 serving: median of the program's ``prefill`` spans in the window (from
joining a slot to the first token, host clock)."""

from perfbench.harness.stats import median


def read(obs):
    ms = [(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in obs.spans
          if s["name"] == "prefill"]
    return median(ms)
