"""device: 1 - the union of device 0's operation intervals over the traced
window (the profiler's trace; the benchmark's ``pb.traced_window`` span)."""


def read(obs):
    if obs.trace is None or obs.window is None:
        return None
    busy_s, window_s, _ = obs.xtrace.busy(
        obs.trace, obs.window, [min(obs.trace["devices"])])
    return 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None
