"""L4 kernels: the sparse layers' grouped products' share of their roofline
inside the token-step program.  The bound is memory: a token step of 128
slots x 8 choices over 256 experts gives an expert a few rows, so the floor
is the weights of every routed expert the step TOUCHED, read once (the
family's flops module).  How many it touched is the program's own count:
every ``decode_step`` span carries ``moe_expert_steps`` and ``moe_untouched``
as they stood behind its step, and the share touched between the window's
first and last span is taken for the traced steps.  Over the chip's HBM
bandwidth, over the device seconds of the grouped-product kernels that ran
inside the traced window's whole token steps (the ``XLA Modules`` events of
the decode program).  A program whose spans carry no such count gives
nothing to read."""


def touched_share(spans):
    counted = sorted((s["args"]["moe_expert_steps"], s["args"]["moe_untouched"])
                     for s in spans if s["name"] == "decode_step"
                     and "moe_expert_steps" in s.get("args", ()))
    if len(counted) < 2 or counted[-1][0] == counted[0][0]:
        return None
    (e0, u0), (e1, u1) = counted[0], counted[-1]
    return 1.0 - (u1 - u0) / (e1 - e0)


def read(obs):
    if (obs.trace is None or obs.window is None
            or "hbm_bytes_per_s" not in obs.peaks
            or not hasattr(obs.flops, "moe_decode_bytes")):
        return None
    import re

    share = touched_share(obs.spans)
    if share is None:
        return None
    dev = obs.trace["devices"][min(obs.trace["devices"])]
    lo, hi = obs.window
    steps = sorted((s, s + d) for n, s, d in dev["modules"]
                   if n.startswith(obs.flops.DECODE_PROGRAM)
                   and s >= lo and s + d <= hi)
    if not steps:
        return None
    rx = re.compile(obs.flops.MOE_KERNELS)
    ops = sorted((s, d) for n, s, d in dev["ops"] if rx.search(n))
    kernel, i = 0, 0
    for s, d in ops:        # both sorted: one pass
        while i < len(steps) and steps[i][1] < s:
            i += 1
        if i < len(steps) and steps[i][0] <= s and s + d <= steps[i][1]:
            kernel += d
    if not kernel:
        return None
    touched = share * len(steps) * obs.flops.sparse_layers(obs.sizes) \
        * obs.sizes["experts"]
    need = obs.flops.moe_decode_bytes(
        obs.sizes, touched,
        obs.flops.ITEMSIZE[obs.cell.config["run"]["param_dtype"]])
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / (kernel / 1e9)
