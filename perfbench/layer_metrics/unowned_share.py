"""L1 graph + compile: share of the traced steps' device time in operations
that no graph op, nor the loss, the metrics or the optimizer update, owns
(instructions XLA made itself: layout copies of parameters, asynchronous
slices), device 0.  Every owner's seconds, layers together and forward and
backward apart, and the six largest kinds of operation with whose they are go
on earlier lines."""

from perfbench.harness import step_owners


def read(obs):
    got = step_owners.read(obs)
    if got is None:
        return None
    seconds = got["seconds"]
    total = sum(seconds.values())
    if not total:
        return None
    owners = {}
    for who in got["by_kind"].values():
        for name, v in who.items():
            owners[name] = owners.get(name, 0.0) + v
    print(f"[{obs.cell.name}] device seconds of {got['steps']} traced steps "
          f"by owner, layers together ({total:.4f} s in all): "
          + ", ".join(f"{name} {v:.4f}" for name, v in
                      sorted(owners.items(), key=lambda kv: -kv[1])),
          flush=True)
    kinds = sorted(got["by_kind"].items(),
                   key=lambda kv: -sum(kv[1].values()))[:6]
    print(f"[{obs.cell.name}] the largest kinds of operation and whose they "
          "are, layers together: " + "; ".join(
              f"{kind} {sum(who.values()):.4f} = " + " + ".join(
                  f"{name} {v:.4f}" for name, v in
                  sorted(who.items(), key=lambda kv: -kv[1])[:4])
              for kind, who in kinds), flush=True)
    return 100.0 * seconds.get((None, None), 0.0) / total
