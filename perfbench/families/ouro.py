"""The Ouro family (looped language models): builds the system's model from a
configuration file through the normal path (``FFConfig.parse_args`` ->
``flexflow_tpu.models.decoder_lm`` builder -> ``compile``), and maps the
reference's weights onto the program's parameters, a leaf at a time.

Every layer is alike (full attention with as many key/value heads as query
heads, a gated SiLU feed-forward, sandwich norms), and the WHOLE list is run
``total_ut_steps`` times a token with the same weights: the builder lays it
that many times (``loops``), the ops of the later passes reading pass 1's
parameters, with the final norm after every pass and an exit gate before the
one head.  ``model.parameters`` therefore holds one layer list's worth, and
``install`` hands each parameter over ONCE, whatever the number of call sites
that read it.

The weights are the reference's (``perfbench/reference/ouro.py``), made from
the seed; the program never makes the weights the benchmark compares.
"""

from __future__ import annotations

REFERENCE = "ouro"
FLOPS = "ouro"


def sizes(config):
    """The normalised sizes the reference and the FLOP count read, from the
    configuration's published keys."""
    run = config["run"]
    kinds = set(config["layer_types"])
    if kinds != {"full_attention"} or config["use_sliding_window"] \
            or len(config["layer_types"]) != int(config["num_hidden_layers"]):
        raise SystemExit(f"perfbench: {config['name']}: the family builds "
                         f"full attention in every layer, got {sorted(kinds)}")
    layers = [{"attention": "full_attention",
               "heads": int(config["num_attention_heads"]), "mlp": "dense"}
              for _ in range(int(config["num_hidden_layers"]))]
    return {"layers": layers, "passes": int(config["total_ut_steps"]),
            "exit_threshold": float(config["early_exit_threshold"]),
            "d_model": int(config["hidden_size"]),
            "head_dim": int(config["head_dim"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "d_ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_theta"]),
            "positions": int(run["max_seq"]),
            "weight_dtype": str(run["param_dtype"])}


def build_serve(config, traffic):
    """The decoder as ``build_decoder_lm`` builds it, compiled for serving
    on one chip; the traced run switches the program's own spans on
    (``FFConfig.trace_sample_rate``)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.decoder_lm import build_decoder_lm

    sz = sizes(config)
    cfg = ff.FFConfig.parse_args([str(a) for a in traffic["program_args"]])
    cfg.compute_dtype = config["run"]["compute_dtype"]
    cfg.param_dtype = config["run"]["param_dtype"]
    cfg.serve_kv_page = int(config["run"]["kv_page_tokens"])
    # a program from before the loop takes neither keyword: it ends here,
    # before any weight is made
    model = build_decoder_lm(
        cfg, sz["layers"], d_model=sz["d_model"], head_dim=sz["head_dim"],
        num_kv_heads=sz["kv_heads"], d_ff=sz["d_ff"], vocab_size=sz["vocab"],
        seq_len=sz["positions"], rms_eps=sz["eps"],
        rope={"full_attention": {"rope_theta": sz["rope_theta"]}},
        sandwich=True, loops=sz["passes"],
        exit_gate=sz["exit_threshold"])[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=ff.MachineMesh({"n": 1}))
    return model


# program parameter (per layer) <- the reference's leaf: "T" = the program
# keeps a kernel (out, in), the reference (in, out)
_LAYER = (("attention_%d/wq", "T", "wq"), ("attention_%d/wk", "T", "wk"),
          ("attention_%d/wv", "T", "wv"), ("attention_%d/wo", "T", "wo"),
          ("ln_attn_%d/scale", "", "g1"), ("ln_attn_out_%d/scale", "", "g1o"),
          ("ln_ffn_%d/scale", "", "g2"), ("ln_ffn_out_%d/scale", "", "g2o"),
          ("ffn_gate_%d/kernel", "T", "w1"), ("ffn_up_%d/kernel", "T", "w3"),
          ("ffn_down_%d/kernel", "T", "w2"))


def leaf_index(sz):
    """``{program parameter: (how, reference leaf, layer or None)}``: one
    entry a PARAMETER, so one a layer whatever the passes."""
    out = {"tok_embedding/table": ("", "tok_emb", None),
           "ln_final/scale": ("", "g_final", None),
           "exit_gate/kernel": ("T", "w_gate", None),
           "exit_gate/bias": ("", "b_gate", None),
           "lm_head/kernel": ("T", "head", None)}
    for i in range(len(sz["layers"])):
        for pat, how, name in _LAYER:
            out[pat % i] = (how, name, i)
    return out


def install(model, sz, ref_params):
    """Hand the seed's weights to the compiled model, a parameter at a
    time: what ``init_layers`` does, with the reference's values.  A
    parameter several call sites read is in ``model.parameters`` once and is
    made once.  Each leaf is made by the reference's OWN program
    (``Params.leaf``) and laid out and cast by a second, small one
    (families/laguna.py says why)."""
    import functools

    import jax

    index = leaf_index(sz)
    names = [p.name for p in model.parameters]
    if sorted(names) != sorted(index):
        raise SystemExit(f"perfbench: parameters without a counterpart in "
                         f"the reference (or held twice): "
                         f"{sorted(set(names) ^ set(index))}")

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def lay(v, how, dtype):
        return (v.T if how == "T" else v).astype(dtype)

    params = {}
    for p in model.parameters:
        how, name, layer = index[p.name]
        dtype = (model.config.param_dtype if p.dtype == "float32"
                 else p.dtype)
        params[p.name] = model._placed_param(
            p, lay(ref_params.leaf(name, layer), how, dtype))
    model._params = params
    model._opt_state = model.optimizer.init_state(
        model._trainable_on_device(params))
    model._step = 0
