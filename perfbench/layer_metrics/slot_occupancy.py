"""L5 serving: mean of ``active`` over the ``decode_step`` spans, over the
engine's slots."""


def read(obs):
    active = [s["args"]["active"] for s in obs.spans
              if s["name"] == "decode_step"]
    slots = obs.counters.get("slots")
    if not active or not slots:
        return None
    return 100.0 * sum(active) / len(active) / slots
