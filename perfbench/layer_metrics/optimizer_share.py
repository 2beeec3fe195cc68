"""L1 graph + compile: share of the traced steps' device time in operations
that the optimizer update owns (the ``optimizer`` scope of the train step),
device 0."""

from perfbench.harness import step_owners


def read(obs):
    got = step_owners.read(obs)
    if got is None:
        return None
    total = sum(got["seconds"].values())
    mine = sum(v for (owner, _), v in got["seconds"].items()
               if owner == "optimizer")
    return 100.0 * mine / total if total else None
