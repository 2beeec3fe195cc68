"""L2 strategy: how far the simulator lies from the traced step op by op.
Over the graph ops that own device time: the gap between the simulated
forward + backward seconds (``Simulator.op_times``, priced as the search
prices them) and the traced seconds a step, weighted by the traced seconds:
sum |simulated - traced| / sum traced.  ``sim_error`` scores the whole step,
where errors of either sign cancel."""

from perfbench.harness import step_owners


def read(obs):
    got = step_owners.read(obs)
    if got is None:
        return None
    from flexflow_tpu.search.simulator import Simulator

    model = got["model"]
    cfg = model.config
    sim = Simulator(num_devices=model.mesh.num_devices,
                    flash_attention=cfg.flash_attention,
                    compute_dtype=cfg.compute_dtype,
                    opt_slot_bytes=model.optimizer.slot_bytes_per_param,
                    use_native=False)
    priced = sim.op_times(model.layers, dict(cfg.strategies))
    traced = {}
    for (owner, _), v in got["seconds"].items():
        if owner in priced:
            traced[owner] = traced.get(owner, 0.0) + v / got["steps"]
    total = sum(traced.values())
    if not total:
        return None
    gap = sum(abs(sum(priced[op]) - t) for op, t in traced.items())
    return 100.0 * gap / total
