"""L1 graph + compile: device seconds attention spends CHOOSING (the
attention ops' ``dsa_index`` scope: the indexer's projections, norm, rotary,
its cache write and its scores; and ``dsa_select``: the choice of ``topk``;
by the programs' owner tables) in prompt chunks and token steps, over the
traced window's busy seconds, device 0: what a learned selection costs beside
the core it makes cheaper (a loop's own instruction, which spans its body's
events, left out: ``sparse_decode_roofline.part_seconds``).  A program without
those scopes gives nothing to read."""

from perfbench.harness import serve_owners
from perfbench.layer_metrics.sparse_decode_roofline import part_seconds


def read(obs):
    got = serve_owners.read(obs)
    if got is None:
        return None
    busy = obs.xtrace.busy_seconds(obs.trace, obs.window)
    mine = part_seconds(got, ("chunk", "token"), ("dsa_index", "dsa_select"))
    return 100.0 * mine / busy if mine and busy else None
