"""L2 strategy: how far the simulator's predicted iteration time for the
strategy that ran lies from the measured step, |simulated - step_ms| / step_ms."""


def read(obs):
    prog, sim = obs.counters.get("step_program"), obs.counters.get("sim_step_s")
    if obs.trace is None or prog is None or sim is None:
        return None
    step = obs.xtrace.step_ms(obs.trace, prog)
    if not step:
        return None
    return 100.0 * abs(sim * 1e3 - step) / step
