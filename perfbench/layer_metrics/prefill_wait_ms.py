"""L5 serving: median of the program's ``prefill_wait`` spans in the window
(from joining a slot to the dispatch of the request's first prefill chunk:
the boundaries a joined request stands in line for, host clock)."""

from perfbench.harness.stats import median


def read(obs):
    return median([(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in obs.spans
                   if s["name"] == "prefill_wait"])
