"""L4 kernels: the paged decode attention kernel's share of its roofline.
The bound is memory: a decode step reads every live position's K and V once
for a handful of operations each.  The bytes are counted from the traffic
(the clients' records of the traced window: each decoded token's prompt and
the tokens before it, perfbench/flops), not from the kernel's pages, so the
same work reads the same whatever implements it; over the chip's HBM
bandwidth, over the kernel's device seconds in the traced window."""


def read(obs):
    work = obs.counters.get("traced_work")
    if obs.trace is None or not work or "hbm_bytes_per_s" not in obs.peaks:
        return None
    kernel = obs.xtrace.op_seconds(
        obs.trace, obs.flops.PAGED_DECODE_KERNELS, obs.window)
    if not kernel or not work["live_positions"]:
        return None
    need = obs.flops.decode_kv_bytes(
        obs.sizes, work["live_positions"],
        obs.flops.ITEMSIZE[obs.cell.config["run"]["compute_dtype"]])
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / kernel
