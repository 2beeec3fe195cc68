"""L5 serving: 50th percentile, over the requests submitted inside the
window, of the client-side time from ``submit()`` to the first token out of
the stream iterator; a failed request counts as never answered.  The steadier
companion of the end-to-end ``ttft_p95_ms``: the typical wait for the one
prefill a step boundary admits."""

from perfbench.harness.stats import quantile


def read(obs):
    ms = obs.counters.get("ttft_ms")
    return quantile(ms, 0.5) if ms else None
