"""L4 kernels: device time in the Mosaic flash-attention calls (forward,
dK/dV, dQ) over device busy time, device 0."""


def read(obs):
    prog = obs.counters.get("step_program")
    if obs.trace is None or prog is None:
        return None
    span = obs.xtrace.module_span(obs.trace, prog)
    kernel = obs.xtrace.op_seconds(obs.trace, obs.flops.FLASH_KERNELS, span)
    busy = obs.xtrace.busy_seconds(obs.trace, span)
    return 100.0 * kernel / busy if kernel and busy else None
