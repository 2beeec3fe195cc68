"""L1 graph + compile: a looped stack's TOKEN STEP's share of its memory
roofline.  The bound is memory: a token step of a dozen slots does a few
operations a byte, so its floor is the bytes it MUST read by the family's
count (``flops.token_step_bytes``): the layer list's weights once a PASS, the
head once, and the K and V rows of the decoded tokens' live positions at every
call site (counted from the clients' records of the traced window, never from
the program's pages, so the same work reads the same whatever implements it);
over the chip's HBM bandwidth, over the device seconds of the traced window's
whole token-step programs (``XLA Modules`` events of ``flops.DECODE_PROGRAM``).

Why the weights count once a pass and not once a step: pass ``t + 1`` starts
from the normed state pass ``t`` ended in, so no layer of it can run before
the whole of pass ``t`` has, and between two uses of one layer's weights lie
the other layers' (4.9 GB in all at the published size against 128 MiB of fast
memory): nothing read in one pass is still on the chip for the next.  While
every pass is run the share cannot pass 100 %; a program that runs fewer
passes than the configuration states would read over it, and
``loop_passes_per_token`` says so first.  A family without such a count, or a
window without a whole token step, gives nothing to read."""


def read(obs):
    work = obs.counters.get("traced_work")
    if (obs.trace is None or obs.window is None or not work
            or "hbm_bytes_per_s" not in obs.peaks
            or not hasattr(obs.flops, "token_step_bytes")):
        return None
    dev = obs.trace["devices"][min(obs.trace["devices"])]
    lo, hi = obs.window
    steps = [d for n, s, d in dev["modules"]
             if n.startswith(obs.flops.DECODE_PROGRAM)
             and s >= lo and s + d <= hi]
    if not steps:
        return None
    need = obs.flops.token_step_bytes(
        obs.sizes, len(steps), work["live_positions"],
        obs.flops.ITEMSIZE[obs.cell.config["run"]["param_dtype"]])
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / (sum(steps) / 1e9)
