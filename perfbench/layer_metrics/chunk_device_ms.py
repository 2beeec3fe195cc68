"""L5 serving: median device time of one prefill-chunk program on device 0
(the profiler trace's ``XLA Modules`` line, the events wholly inside the
traced window), all chunk buckets together: the device-side twin of
``prefill_exec_ms``, as ``decode_device_ms`` is ``decode_step_ms``'s.  The
programs are the ones the engine's ``gen-prefill`` phase spans name (one a
bucket); a program whose chunk spans name none (one from before the names,
whose buckets all print as ``jit_prefill``) is read by the name its
``prefill_exec`` spans carry.  Nothing where no span names a program of the
window.  Each program's events and median go on an earlier line."""

from perfbench.harness.stats import median


def _named(spans, name):
    return {s["args"]["program"] for s in spans
            if s["name"] == name and (s.get("args") or {}).get("program")}


def read(obs):
    if obs.trace is None or obs.window is None:
        return None
    named = (_named(obs.spans, "gen-prefill")
             or _named(obs.spans, "prefill_exec"))
    lo, hi = obs.window
    by_program = {}
    for name, s, d in obs.trace["devices"][min(obs.trace["devices"])][
            "modules"]:
        program = name.split("(", 1)[0]
        if program in named and s >= lo and s + d <= hi:
            by_program.setdefault(program, []).append(d / 1e6)
    if not by_program:
        return None
    print(f"[{obs.cell.name}] chunk_device_ms: events and median ms a "
          "program: " + ", ".join(
              f"{p} x{len(ms)} {median(ms):.3f}"
              for p, ms in sorted(by_program.items())), flush=True)
    return median([ms for v in by_program.values() for ms in v])
