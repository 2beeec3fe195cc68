"""L1 graph + compile: device seconds the attention ops own inside the
traced window's serving programs (kernel, projections, rotary, gate, cache
write; prompt chunks and token steps alike; by the programs' owner tables)
over the traced window's busy seconds, device 0: ``flash_share``'s serving
twin, which the paged decode kernel's name alone understates by the
projections and, in a chunk, misses altogether.  The share is of ALL busy
seconds, so the programs the window's edges cut and what runs between
programs count below the line and not above it: understated by at most two
programs' worth.  Attention ops are the ones called ``attention_<n>``; a
program without owner tables (one from before the scopes, which the
benchmark also runs) gives them nothing, and that reads 0, as the definition
says.  Nothing where there is no device trace."""

from perfbench.harness import serve_owners


def read(obs):
    got = serve_owners.read(obs)
    if got is None:
        return None
    busy = obs.xtrace.busy_seconds(obs.trace, obs.window)
    if not busy:
        return None
    mine = sum(v for (_, who, _), v in got["seconds"].items()
               if who == "attention")
    return 100.0 * mine / busy
