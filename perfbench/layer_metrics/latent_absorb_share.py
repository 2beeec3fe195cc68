"""L1 graph + compile: device seconds the latent attention ops spend
REASSOCIATING (their ``mla_absorb`` scope: ``W_UK`` and ``W_UV`` applied to
the queries and the results of a token step, the cached history's expansion
to per-head keys and values in a prompt chunk; by the programs' owner tables)
over the traced window's busy seconds, device 0: what the form each step took
costs beside its core, the number that says whether the chunk took the right
one.  A program without that scope gives nothing to read."""

from perfbench.harness import serve_owners


def read(obs):
    got = serve_owners.read(obs)
    if got is None:
        return None
    busy = obs.xtrace.busy_seconds(obs.trace, obs.window)
    mine = sum(v for (_, who, part), v in got["seconds"].items()
               if who == "attention" and part == "mla_absorb")
    return 100.0 * mine / busy if mine and busy else None
