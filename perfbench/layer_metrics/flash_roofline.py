"""L4 kernels: the flash-attention kernels' share of their roofline.  The
operations attention needs forward + backward (scores and values, two
products forward and four backward, from perfbench/flops; the kernel's
recomputation of the scores is not counted) over the chip's bf16 peak, over
the kernels' device time.  The bound is compute: at head size 64 and 512
keys a block's operands are read once from HBM per 2 x 512 operations each."""


def read(obs):
    prog = obs.counters.get("step_program")
    if obs.trace is None or prog is None or not obs.peaks:
        return None
    kernel = obs.xtrace.op_seconds(
        obs.trace, obs.flops.FLASH_KERNELS, obs.xtrace.module_span(obs.trace, prog))
    steps = len(obs.xtrace.module_times_ms(obs.trace, prog))
    if not kernel or not steps:
        return None
    seq = int(obs.cell.traffic["seq_len"])
    need = steps * obs.flops.attention_train_flops(
        obs.sizes, obs.counters["tokens_per_step"] // seq, seq)
    need /= obs.counters["chips"]      # device 0 does its share
    return 100.0 * need / obs.peaks["bf16_flops"] / kernel
