"""L1 graph + compile: end-to-end model FLOP/s utilization of the traced
window.  The benchmark's own count of the operations forward + backward need
(perfbench/flops, no recomputation counted) x the tokens per second of the
traced steps (first program start to last program end on device 0) over
chips x the chip's published bf16 peak.  Not a kernel's roofline share."""


def read(obs):
    prog = obs.counters.get("step_program")
    if obs.trace is None or prog is None or not obs.peaks:
        return None
    n = len(obs.xtrace.module_times_ms(obs.trace, prog))
    if n < 2:
        return None
    first, last = obs.xtrace.module_span(obs.trace, prog)
    seconds = (last - first) / 1e9
    seq = int(obs.cell.traffic["seq_len"])
    tokens_per_s = n * obs.counters["tokens_per_step"] / seconds
    need = obs.flops.train_flops_per_token(obs.sizes, seq) * tokens_per_s
    return 100.0 * need / (obs.counters["chips"] * obs.peaks["bf16_flops"])
