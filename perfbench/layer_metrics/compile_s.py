"""L0 entry: seconds jax spent in backend compiles during set-up (a
persistent-cache hit is a short one), from jax.monitoring."""


def read(obs):
    return obs.counters.get("compile_s")
