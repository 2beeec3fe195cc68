"""L1 graph + compile: end-to-end model FLOP/s utilization of the traced
window of a served model.  The benchmark's own count of the forward
operations the window's traffic needs (perfbench/flops: every token decoded
at its own live length, every prompt prefilled, the LM head; from the
clients' records) over the traced window's seconds and the chip's published
bf16 peak.  Not a kernel's roofline share: it bounds them all."""


def read(obs):
    work = obs.counters.get("traced_work")
    if obs.trace is None or obs.window is None or not work \
            or "bf16_flops" not in obs.peaks:
        return None
    need = obs.flops.serve_flops(obs.sizes, work["decode_tokens"],
                                 work["live_positions"], work["prompt_lens"])
    seconds = (obs.window[1] - obs.window[0]) / 1e9
    if not need or seconds <= 0:
        return None
    return 100.0 * need / seconds / (obs.counters["chips"]
                                     * obs.peaks["bf16_flops"])
