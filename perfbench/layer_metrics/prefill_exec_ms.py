"""L5 serving: median of the program's ``prefill_exec`` spans in the window
(from the dispatch of the request's first prefill chunk to its first token
on the host: the work of a prefill, host clock)."""

from perfbench.harness.stats import median


def read(obs):
    return median([(s["t1_ns"] - s["t0_ns"]) / 1e6 for s in obs.spans
                   if s["name"] == "prefill_exec"])
