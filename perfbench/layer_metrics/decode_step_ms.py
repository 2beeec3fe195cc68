"""L5 serving: median of the program's ``decode_step`` spans in the window
(host clock, one dispatch and one token fetch each)."""

from perfbench.harness.stats import median


def read(obs):
    ms = [(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in obs.spans
          if s["name"] == "decode_step"]
    return median(ms)
