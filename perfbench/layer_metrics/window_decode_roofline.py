"""L4 kernels: the paged decode attention kernel's share of its roofline in
a model whose layers differ in what they read: every live position in the
full-attention layers, the window in the sliding ones.  The bound is memory.
The bytes are counted from the traffic (the clients' records of the traced
window: each decoded token's live positions, and the window once a decoded
token a sliding layer; the family's flops module), never from the kernel's
pages, over the chip's HBM bandwidth, over the kernel's device seconds in
the traced window."""


def read(obs):
    work = obs.counters.get("traced_work")
    if (obs.trace is None or not work or "hbm_bytes_per_s" not in obs.peaks
            or not hasattr(obs.flops, "window_decode_kv_bytes")):
        return None
    kernel = obs.xtrace.op_seconds(
        obs.trace, obs.flops.PAGED_DECODE_KERNELS, obs.window)
    if not kernel or not work["live_positions"]:
        return None
    need = obs.flops.window_decode_kv_bytes(
        obs.sizes, work["live_positions"], work["decode_tokens"],
        obs.flops.ITEMSIZE[obs.cell.config["run"]["kv_dtype"]])
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / kernel
