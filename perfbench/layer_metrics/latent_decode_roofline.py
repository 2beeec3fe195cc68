"""L4 kernels: the token steps' latent attention core's share of its
roofline.  On a v5e this core sits on the ridge (2 x 128 heads x 1 088
operations over the 1 152 B a cached position holds), so the need is the
LARGER of the two bounds: the bytes of every live position's latent row once a
layer over the chip's HBM bandwidth, and the absorbed scores and values over
those rows over the chip's bf16 peak.  Both are counted from the traffic (the
clients' records of the traced window: each decoded token's prompt and the
tokens before it; the family's flops module), never from the kernel's pages
or the stored width.  The seconds are what the programs' owner tables give
the attention ops' ``mla_core`` scope inside the traced window's token steps
(``perfbench/harness/serve_owners.py``), so the same work reads the same
whatever implements the core.  A program without that scope gives nothing to
read."""

from perfbench.harness import serve_owners


def read(obs):
    work = obs.counters.get("traced_work")
    if (not work or not work["live_positions"]
            or not hasattr(obs.flops, "latent_decode_need")
            or not {"hbm_bytes_per_s", "bf16_flops"} <= set(obs.peaks)):
        return None
    got = serve_owners.read(obs)
    if got is None:
        return None
    core = got["seconds"].get(("token", "attention", "mla_core"))
    if not core:
        return None
    nbytes, ops = obs.flops.latent_decode_need(
        obs.sizes, work["live_positions"],
        obs.flops.ITEMSIZE[obs.cell.config["run"]["kv_dtype"]])
    need = max(nbytes / obs.peaks["hbm_bytes_per_s"],
               ops / obs.peaks["bf16_flops"])
    return 100.0 * need / core
