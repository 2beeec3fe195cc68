"""L2 strategy: seconds in the strategy search inside compile() (the
benchmark's span round ``optimize_strategies``); nothing to read in a cell
that runs no search."""


def read(obs):
    return obs.counters.get("search_s")
