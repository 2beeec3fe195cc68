"""The traced train steps' device time summed by the graph op that owns it,
for the per-layer metrics that read it (``optimizer_share``,
``unowned_share``, ``sim_error_by_op``).

The driver deletes its model before the readers run, so the cell's model is
built again through the family, and the program maps every instruction of
the compiled step to its owner (``FFModel.step_op_table``, from the scopes
the step was traced under).  Once per run: the first reader that asks pays
for it, the others find it on ``obs``.
"""

from __future__ import annotations

import re

import numpy as np


def read(obs):
    """``{"seconds": {(owner, "fwd"|"bwd"|None): s}, "by_kind": {operation
    kind: {owner with its layer number taken off: s}}, "steps": n, "model":
    the model}`` for device 0's operations inside the whole traced steps, or
    None where there is no device trace or the program cannot say who owns
    what (a program older than ``step_op_table``)."""
    if not hasattr(obs, "_step_owners"):
        obs._step_owners = _build(obs)
    return obs._step_owners


def _build(obs):
    prog = obs.counters.get("step_program")
    if obs.trace is None or prog is None:
        return None
    span = obs.xtrace.module_span(obs.trace, prog)
    steps = len(obs.xtrace.module_times_ms(obs.trace, prog))
    if span is None or not steps:
        return None
    from flexflow_tpu.model import FFModel

    if not hasattr(FFModel, "step_op_table"):
        return None
    from flexflow_tpu.obs.device_ops import attribute

    cell, tr = obs.cell, obs.cell.traffic
    fam = cell.module("families", cell.config["family"])
    model = fam.build_train(cell.config, tr, {})
    model.init_layers(seed=0)
    b, s = int(tr["global_batch"]), int(tr["seq_len"])
    table = model.step_op_table(np.zeros((b, s), np.int32),
                                np.zeros((b, 1), np.int32))
    if not any(owner for owner, _ in table.values()):
        # an executable that the compilation cache kept from before the
        # program traced its step under scopes: jax leaves metadata out of
        # the cache's key.  Never guessed round
        raise SystemExit(
            "perfbench: the compiled train step names no graph op in its "
            "metadata; it was loaded from a compilation cache written "
            "before the step had scopes: clear the cache directory")
    device = min(obs.trace["devices"])
    ops = [e for e in obs.trace["devices"][device]["ops"]
           if e[1] >= span[0] and e[1] + e[2] <= span[1]]
    by_kind = {}
    for name, _, dur in ops:
        owner, phase = table.get(name, (None, None))
        who = (re.sub(r"_\d+$", "", owner) if owner else "nobody") + (
            "." + phase if phase else "")
        kind = by_kind.setdefault(obs.xtrace.op_kind(name), {})
        kind[who] = kind.get(who, 0.0) + dur / 1e9
    return {"seconds": attribute(ops, table), "by_kind": by_kind,
            "steps": steps, "model": model}
