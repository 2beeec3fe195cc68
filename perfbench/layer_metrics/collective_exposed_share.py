"""L3 placement: share of the traced window in which device 0 runs a
collective (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all) and nothing else."""


def read(obs):
    if obs.trace is None or obs.window is None:
        return None
    seconds = (obs.window[1] - obs.window[0]) / 1e9
    return 100.0 * obs.xtrace.exposed_collective_seconds(obs.trace) / seconds
