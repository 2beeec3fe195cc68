"""L5 serving: median device time of one decode program on device 0 (the
profiler trace's ``XLA Modules`` line), the program being the one the
engine's ``decode_step`` spans name: the device-side twin of
``decode_step_ms``, which also holds the dispatch and the token fetch."""

import collections

from perfbench.harness.stats import median


def read(obs):
    programs = collections.Counter(
        s["args"]["program"] for s in obs.spans
        if s["name"] == "decode_step" and "program" in s.get("args", {}))
    if obs.trace is None or not programs:
        return None
    program = programs.most_common(1)[0][0]
    return median(obs.xtrace.module_times_ms(obs.trace, program + "("))
