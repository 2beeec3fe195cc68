"""L0 entry: backend compiles during set-up that the persistent compilation
cache did not serve.  0 in every run of a cell after its first in a checkout."""


def read(obs):
    v = obs.counters.get("cache_misses")
    return None if v is None else float(v)
