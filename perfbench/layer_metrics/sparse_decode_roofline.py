"""L4 kernels: the token steps' SPARSE attention's share of its roofline.
The bound is memory: a decoded token scores every live position of its slot
by the indexer's 64-value key and then reads the K and V rows of the ``topk``
it chose, so the need is the indexer keys of the traced steps' live positions
and the chosen rows' K and V, a layer (the family's flops module; counted from
the clients' records of the traced window, never from the program's pages or
stored widths), over the chip's HBM bandwidth.  The seconds are what the
programs' owner tables give the attention ops' ``dsa_index``, ``dsa_select``
and ``dsa_core`` scopes inside the traced window's token steps
(``perfbench/harness/serve_owners.py``): the same work reads the same
whatever implements it, so a core that reads every live page and masks reads
low, honestly.  A loop's own instruction (``while``, ``conditional``) is an
event that SPANS the events of its body, which the table gives to the same
part: it is left out, so that the body counts once.  A program without those
scopes gives nothing to read."""

from perfbench.harness import serve_owners

PARTS = ("dsa_index", "dsa_select", "dsa_core")
SPANNING = ("while", "conditional")


def part_seconds(got, kinds, parts):
    """Device seconds the attention ops' ``parts`` own in programs of
    ``kinds`` (``chunk``, ``token``), the instructions that span their
    bodies' events left out."""
    total = sum(v for (kind, who, part), v in got["seconds"].items()
                if kind in kinds and who == "attention" and part in parts)
    labels = {f"{kind}:attention.{part}" for kind in kinds for part in parts}
    return total - sum(v for op in SPANNING
                       for label, v in got["by_kind"].get(op, {}).items()
                       if label in labels)


def read(obs):
    work = obs.counters.get("traced_work")
    if (not work or not work["live_positions"]
            or not hasattr(obs.flops, "sparse_decode_bytes")
            or "hbm_bytes_per_s" not in obs.peaks):
        return None
    got = serve_owners.read(obs)
    if got is None:
        return None
    seconds = part_seconds(got, ("token",), PARTS)
    if not seconds:
        return None
    need = obs.flops.sparse_decode_bytes(
        obs.sizes, work["live_positions"], work["decode_tokens"],
        obs.flops.ITEMSIZE[obs.cell.config["run"]["kv_dtype"]])
    return 100.0 * need / obs.peaks["hbm_bytes_per_s"] / seconds
