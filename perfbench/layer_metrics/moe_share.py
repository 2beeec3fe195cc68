"""L1 graph + compile: the sparse layers' grouped products' device seconds,
prompt chunks and token steps alike, over the traced window's busy seconds,
device 0: how much of what the chip does is the experts."""


def read(obs):
    if (obs.trace is None or obs.window is None
            or not hasattr(obs.flops, "MOE_KERNELS")):
        return None
    kernel = obs.xtrace.op_seconds(obs.trace, obs.flops.MOE_KERNELS,
                                   obs.window)
    busy = obs.xtrace.busy_seconds(obs.trace, obs.window)
    return 100.0 * kernel / busy if kernel and busy else None
