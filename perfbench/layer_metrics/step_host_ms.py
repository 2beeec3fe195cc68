"""L5 serving: what the host alone costs a step boundary.  Per boundary (the
engine's phase spans that share a ``step``), the summed phases in which
nothing was handed to the device and nothing awaited from it; the median over
the window's boundaries (host clock).  The per-phase medians go on an earlier
line."""

from perfbench.harness.stats import median

# dispatching a program, waiting for its result, and waiting for work are
# not the host's own cost
NOT_HOST_ONLY = ("gen-prefill", "generate.dispatch", "generate.fetch",
                 "generate.idle")


def read(obs):
    by_step, by_phase = {}, {}
    for s in obs.spans:
        if s.get("cat") != "engine" or s["name"] in NOT_HOST_ONLY:
            continue
        ms = (s["t1_ns"] - s["t0_ns"]) / 1e6
        step = s["args"]["step"]
        by_step[step] = by_step.get(step, 0.0) + ms
        by_phase.setdefault(s["name"], []).append(ms)
    if not by_step:
        return None
    print(f"[{obs.cell.name}] step_host_ms over {len(by_step)} boundaries; "
          "median ms of each phase where it ran: "
          + ", ".join(f"{name} {median(v):.3f} (x{len(v)})"
                      for name, v in sorted(by_phase.items())), flush=True)
    return median(by_step.values())
