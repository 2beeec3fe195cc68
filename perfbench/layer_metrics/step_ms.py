"""L1 graph + compile: median device time of one train-step program on
device 0, from the profiler trace's ``XLA Modules`` line."""


def read(obs):
    prog = obs.counters.get("step_program")
    if obs.trace is None or prog is None:
        return None
    return obs.xtrace.step_ms(obs.trace, prog)
