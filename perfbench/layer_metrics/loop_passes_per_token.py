"""L5 serving: the passes of a looped stack a served token went through, by
the program's own count.  The exit gate counts on the device, for every live
row it serves, the token and the passes it was run through; every
``decode_step`` span carries both as they stood behind its step
(``loop_tokens``, ``loop_passes``), and the reading is the passes over the
tokens between the window's first and last span: 4.0 where the configuration
states four passes and every one is run.  The guard that no later program
serves faster by running fewer passes than the configuration states.  A
program whose spans carry no such count gives nothing to read."""


def read(obs):
    counted = sorted((s["args"]["loop_tokens"], s["args"]["loop_passes"])
                     for s in obs.spans if s["name"] == "decode_step"
                     and "loop_tokens" in s.get("args", ()))
    if len(counted) < 2 or counted[-1][0] == counted[0][0]:
        return None
    (t0, p0), (t1, p1) = counted[0], counted[-1]
    return (p1 - p0) / (t1 - t0)
