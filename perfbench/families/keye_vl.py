"""The Keye-VL-2.0 family's decoder: builds the system's model from a
configuration file through the normal path (``FFConfig.parse_args`` ->
``flexflow_tpu.models.decoder_lm`` builder -> ``compile``), and maps the
reference's weights onto the program's parameters, a leaf at a time.

Every layer is alike: grouped-query attention with QK-norm and a learned
indexer (``sa_config``) that chooses the ``topk`` keys a query attends over,
then 128 routed experts, no shared one, no dense layer.  The vision tower and
image tokens' three position streams are not built (the configuration file
says why); the traffic is text.

The weights are the reference's (``perfbench/reference/keye_vl.py``), made
from the seed; the program never makes the weights the benchmark compares.
``install`` makes each of the program's parameters from the reference's leaf
(or the two it is put together from), lays it out as the program keeps it and
casts it, so that never more than two float32 leaves beside their cast are
alive.
"""

from __future__ import annotations

REFERENCE = "keye_vl"
FLOPS = "keye_vl"


def sizes(config):
    """The normalised sizes the reference and the FLOP count read, from the
    configuration's published keys."""
    run, sa = config["run"], config["sa_config"]
    heads = int(config["num_attention_heads"])
    if int(config["decoder_sparse_step"]) != 1 or config["mlp_only_layers"] \
            or int(sa["indexer_num_kv_heads"]) != 1:
        raise SystemExit(f"perfbench: {config['name']}: the family builds "
                         "every layer sparse and ONE indexer key head")
    layers = [{"attention": "full_attention", "heads": heads, "mlp": "sparse"}
              for _ in range(int(config["num_hidden_layers"]))]
    return {"layers": layers, "d_model": int(config["hidden_size"]),
            "head_dim": int(config["head_dim"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "d_ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "eps": float(config["rms_norm_eps"]),
            "rope_theta": float(config["rope_theta"]),
            "mrope_section": [int(n) for n in
                              config["rope_scaling"]["mrope_section"]],
            "index_heads": int(sa["indexer_num_heads"]),
            "index_dim": int(sa["indexer_head_dim"]),
            "index_eps": float(config["rms_norm_eps"]),
            "topk": int(sa["topk"]),
            "experts": int(config["num_experts"]),
            "k": int(config["num_experts_per_tok"]),
            "expert_ff": int(config["moe_intermediate_size"]),
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
    # a program from before the learned selection takes neither keyword: it
    # ends here, before any weight is made
    model = build_decoder_lm(
        cfg, sz["layers"], d_model=sz["d_model"], head_dim=sz["head_dim"],
        num_kv_heads=sz["kv_heads"], d_ff=sz["d_ff"], vocab_size=sz["vocab"],
        seq_len=sz["positions"], rms_eps=sz["eps"],
        rope={"full_attention": {"rope_theta": sz["rope_theta"]}},
        qk_norm=sz["eps"],
        sparse={"index_heads": sz["index_heads"],
                "index_dim": sz["index_dim"], "topk": sz["topk"],
                "eps": sz["index_eps"]},
        moe={"num_experts": sz["experts"], "k": sz["k"],
             "d_ff": sz["expert_ff"], "shared_d_ff": 0,
             "routed_scale": 1.0})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=ff.MachineMesh({"n": 1}))
    return model


# program parameter (per layer) <- how it is made of the reference's leaves:
# "T" = the program keeps a kernel (out, in), the reference (in, out);
# "cat" = two leaves side by side on the last dim (gate | up)
_LAYER = (("attention_%d/wq", "T", ("wq",)), ("attention_%d/wk", "T", ("wk",)),
          ("attention_%d/wv", "T", ("wv",)), ("attention_%d/wo", "T", ("wo",)),
          ("attention_%d/q_norm", "", ("gq",)),
          ("attention_%d/k_norm", "", ("gk",)),
          ("attention_%d/wiq", "T", ("wiq",)),
          ("attention_%d/wik", "T", ("wik",)),
          ("attention_%d/wiw", "T", ("wiw",)),
          ("attention_%d/ik_norm", "", ("gik",)),
          ("attention_%d/ik_bias", "", ("bik",)),
          ("ln_attn_%d/scale", "", ("g1",)), ("ln_ffn_%d/scale", "", ("g2",)),
          ("moe_%d/gate", "T", ("wr",)), ("moe_%d/w_up", "cat", ("e1", "e3")),
          ("moe_%d/w_down", "", ("e2",)))


def leaf_index(sz):
    """``{program parameter: (how, reference leaves, layer or None)}``."""
    out = {"tok_embedding/table": ("", ("tok_emb",), None),
           "ln_final/scale": ("", ("g_final",), None),
           "lm_head/kernel": ("T", ("head",), None)}
    for i in range(len(sz["layers"])):
        for pat, how, leaves in _LAYER:
            out[pat % i] = (how, leaves, i)
    return out


def install(model, sz, ref_params):
    """Hand the seed's weights to the compiled model, a parameter at a
    time: what ``init_layers`` does, with the reference's values.  Each
    leaf is made by the reference's OWN program (``Params.leaf``) and laid
    out and cast by a second, small one (families/laguna.py says why)."""
    import functools

    import jax
    import jax.numpy as jnp

    index = leaf_index(sz)
    names = {p.name for p in model.parameters}
    if names != set(index):
        raise SystemExit(f"perfbench: parameters without a counterpart in "
                         f"the reference: {sorted(names ^ set(index))}")

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def lay(parts, how, dtype):
        v = parts[0] if how != "cat" else jnp.concatenate(parts, axis=-1)
        return (v.T if how == "T" else v).astype(dtype)

    params = {}
    for p in model.parameters:
        how, leaves, layer = index[p.name]
        dtype = (model.config.param_dtype if p.dtype == "float32"
                 else p.dtype)
        parts = [ref_params.leaf(n, layer) for n in leaves]
        params[p.name] = model._placed_param(p, lay(parts, how, dtype))
        del parts
    model._params = params
    model._opt_state = model.optimizer.init_state(
        model._trainable_on_device(params))
    model._step = 0
