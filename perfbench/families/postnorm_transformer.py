"""The post-norm transformer family: builds the system's model from a
configuration file through the normal path (``FFConfig.parse_args`` ->
``flexflow_tpu.models.transformer`` builder -> ``compile``), and maps the
reference's weights onto the program's parameters.

The weights are the reference's (``perfbench/reference``), made from the seed;
the program never makes the weights the benchmark compares.
"""

from __future__ import annotations

import time

REFERENCE = "postnorm_transformer"
FLOPS = "postnorm_transformer"


def sizes(config):
    """The normalised sizes the reference and the FLOP count read, taken
    from the configuration's published keys through its ``keys`` map."""
    k = config["keys"]
    run = config["run"]
    sz = {name: int(config[k[name]]) for name in
          ("layers", "d_model", "heads", "d_ff", "vocab", "positions")}
    sz.update(causal=bool(run["causal"]), head=str(run["head"]),
              eps=float(run["layer_norm_eps"]),
              num_labels=int(run.get("num_labels", 0)))
    return sz


def _ff_config(config, traffic):
    import flexflow_tpu as ff

    cfg = ff.FFConfig.parse_args([str(a) for a in traffic["program_args"]])
    cfg.compute_dtype = config["run"]["compute_dtype"]
    cfg.param_dtype = config["run"]["param_dtype"]
    return cfg


def build_train(config, traffic, counters):
    """The encoder as ``examples/apps/transformer.py`` builds it.  The search
    inside ``compile()`` (when the traffic's arguments ask for one) is timed
    by a span of the benchmark's own round ``optimize_strategies``."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.transformer import build_transformer
    from flexflow_tpu.search import mcmc

    sz = sizes(config)
    cfg = _ff_config(config, traffic)
    model, _, logits = build_transformer(
        cfg, num_layers=sz["layers"], d_model=sz["d_model"],
        num_heads=sz["heads"], d_ff=sz["d_ff"], seq_len=traffic["seq_len"],
        vocab_size=sz["vocab"], num_classes=sz["num_labels"])
    inner = mcmc.optimize_strategies

    def timed(*a, **kw):
        t0 = time.perf_counter()
        try:
            return inner(*a, **kw)
        finally:
            counters["search_s"] = time.perf_counter() - t0

    mcmc.optimize_strategies = timed
    try:
        model.compile(ff.AdamOptimizer(**traffic["adam"]),
                      ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                      [ff.METRICS_ACCURACY], final_tensor=logits)
    finally:
        mcmc.optimize_strategies = inner
    return model


def build_serve(config, traffic):
    """The causal LM as ``build_transformer_lm`` builds it, compiled for
    serving on one chip; the traced run switches the program's own spans on
    (``FFConfig.trace_sample_rate``)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.transformer import build_transformer_lm

    sz = sizes(config)
    cfg = _ff_config(config, traffic)
    model = build_transformer_lm(
        cfg, num_layers=sz["layers"], d_model=sz["d_model"],
        num_heads=sz["heads"], d_ff=sz["d_ff"], seq_len=sz["positions"],
        vocab_size=sz["vocab"])[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=ff.MachineMesh({"n": 1}))
    return model


# program parameter (per layer) <- reference leaf; True = the program keeps
# the kernel as (out, in), the reference as (in, out)
_PER_LAYER = (("attention_%d/wq", "wq", True), ("attention_%d/wk", "wk", True),
              ("attention_%d/wv", "wv", True), ("attention_%d/wo", "wo", True),
              ("attention_%d/bias", "bo", False),
              ("ln_attn_%d/scale", "ln1_g", False),
              ("ln_attn_%d/bias", "ln1_b", False),
              ("ffn_up_%d/kernel", "w1", True), ("ffn_up_%d/bias", "b1", False),
              ("ffn_down_%d/kernel", "w2", True),
              ("ffn_down_%d/bias", "b2", False),
              ("ln_ffn_%d/scale", "ln2_g", False),
              ("ln_ffn_%d/bias", "ln2_b", False))


def leaf_index(sz):
    """``{program parameter name: (reference leaf, layer or None)}``."""
    head = "lm_head" if sz["head"] == "lm" else "classifier"
    out = {"tok_embedding/table": ("tok_emb", None),
           "pos_embedding/table": ("pos_emb", None),
           head + "/kernel": ("head_w", None),
           head + "/bias": ("head_b", None)}
    for i in range(sz["layers"]):
        for pat, key, _ in _PER_LAYER:
            out[pat % i] = (key, i)
    return out


def _program_values(sz, ref):
    """Reference weights under the program's parameter names and layouts."""
    transposed = {key for _, key, t in _PER_LAYER if t} | {"head_w"}
    out = {}
    for name, (key, layer) in leaf_index(sz).items():
        v = ref[key] if layer is None else ref[key][layer]
        out[name] = v.T if key in transposed else v
    return out


def in_reference_layout(sz, tree):
    """The program's per-parameter tree with every kernel turned back to
    the reference's (in, out) layout, still under the program's names."""
    transposed = {key for _, key, t in _PER_LAYER if t} | {"head_w"}
    idx = leaf_index(sz)
    return {n: (v.T if idx[n][0] in transposed else v)
            for n, v in tree.items()}


def placed(model, sz, ref_params):
    """The reference's weights as the program's parameters, placed on the
    model's mesh the way ``init_layers`` places its own."""
    import jax

    values = jax.jit(lambda r: _program_values(sz, r))(ref_params)
    names = {p.name for p in model.parameters}
    if names != set(values):
        raise SystemExit(f"perfbench: parameters without a counterpart in "
                         f"the reference: {sorted(names ^ set(values))}")
    return {p.name: model._placed_param(p, values[p.name])
            for p in model.parameters}


def install(model, sz, ref_params):
    """Hand the seed's weights to the compiled model: what ``init_layers``
    does, with the reference's values for its own."""
    model._params = placed(model, sz, ref_params)
    model._opt_state = model.optimizer.init_state(
        model._trainable_on_device(model._params))
    model._step = 0


def simulated_step_s(model):
    """The simulator's predicted seconds per iteration for the strategy
    that ran, priced the way the search prices it (analytic roofline)."""
    from flexflow_tpu.search.simulator import Simulator

    cfg = model.config
    sim = Simulator(num_devices=model.mesh.num_devices,
                    flash_attention=cfg.flash_attention,
                    compute_dtype=cfg.compute_dtype,
                    opt_slot_bytes=model.optimizer.slot_bytes_per_param,
                    use_native=False)
    return float(sim.simulate(model.layers, dict(cfg.strategies),
                              mesh_shape=dict(model.mesh.sizes)))
