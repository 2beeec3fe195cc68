"""The openPangu-Ultra-MoE family: builds the system's model from a
configuration file through the normal path (``FFConfig.parse_args`` ->
``flexflow_tpu.models.decoder_lm`` builder -> ``compile``), and maps the
reference's weights onto the program's parameters, a leaf at a time.

The configuration is ONE CHIP'S SHARE of an expert-parallel deployment: its
``n_routed_experts`` and ``vocab_size`` are what the chip HOLDS (the published
counts stand beside them under ``published``), so ``sizes`` gives ``experts``
(held) beside ``router_experts`` (all the router scores) and ``vocab`` the
held slice, from which the driver draws its token ids.

The weights are the reference's (``perfbench/reference/pangu_moe.py``), made
from the seed; the program never makes the weights the benchmark compares.
``install`` makes each of the program's parameters from the reference's leaf
(or the two it is put together from), lays it out as the program keeps it and
casts it, so that never more than two float32 leaves beside their cast are
alive.
"""

from __future__ import annotations

REFERENCE = "pangu_moe"
FLOPS = "pangu_moe"


def sizes(config):
    """The normalised sizes the reference and the FLOP count read, from the
    configuration's published keys."""
    run = config["run"]
    n = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    heads = int(config["num_attention_heads"])
    layers = [{"attention": "latent_attention", "heads": heads,
               "mlp": "dense" if i < dense else "sparse"} for i in range(n)]
    return {"layers": layers, "d_model": int(config["hidden_size"]),
            "q_rank": int(config["q_lora_rank"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "rope_theta": float(config["rope_theta"]),
            "d_ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "eps": float(config["rms_norm_eps"]),
            "experts": int(config["n_routed_experts"]),
            "router_experts": int(config["published"]["n_routed_experts"]),
            "first_expert": int(run["first_expert"]),
            "k": int(config["num_experts_per_tok"]),
            "expert_ff": int(config["moe_intermediate_size"]),
            "shared_ff": int(config["moe_intermediate_size"])
            * int(config["n_shared_experts"]),
            "routed_scale": float(config["routed_scaling_factor"]),
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
    # a program from before latent layers takes neither keyword: it ends
    # here, before any weight is made
    model = build_decoder_lm(
        cfg, sz["layers"], d_model=sz["d_model"], head_dim=0, num_kv_heads=0,
        d_ff=sz["d_ff"], vocab_size=sz["vocab"], seq_len=sz["positions"],
        rms_eps=sz["eps"], sandwich=bool(config["sandwich_norm"]),
        latent={"q_rank": sz["q_rank"], "kv_rank": sz["kv_rank"],
                "nope_dim": sz["nope"], "rope_dim": sz["rope"],
                "v_dim": sz["v"], "rope_theta": sz["rope_theta"]},
        moe={"num_experts": sz["router_experts"], "k": sz["k"],
             "d_ff": sz["expert_ff"], "shared_d_ff": sz["shared_ff"],
             "routed_scale": sz["routed_scale"], "scoring": "sigmoid",
             "held": (sz["first_expert"], sz["experts"])})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=ff.MachineMesh({"n": 1}))
    return model


# program parameter (per layer) <- how it is made of the reference's leaves:
# "T" = the program keeps a kernel (out, in), the reference (in, out);
# "cat" = two leaves side by side on the last dim (gate | up)
_ATTENTION = (("attention_%d/wq_a", "T", ("wqa",)),
              ("attention_%d/q_norm", "", ("gq",)),
              ("attention_%d/wq_b", "T", ("wqb",)),
              ("attention_%d/wkv_a", "T", ("wkva",)),
              ("attention_%d/kv_norm", "", ("gkv",)),
              ("attention_%d/wkv_b", "T", ("wkvb",)),
              ("attention_%d/wo", "T", ("wo",)),
              ("ln_attn_%d/scale", "", ("g1",)),
              ("ln_attn_out_%d/scale", "", ("g2",)),
              ("ln_ffn_%d/scale", "", ("g3",)),
              ("ln_ffn_out_%d/scale", "", ("g4",)))
_DENSE = (("ffn_gate_%d/kernel", "T", ("w1",)), ("ffn_up_%d/kernel", "T", ("w3",)),
          ("ffn_down_%d/kernel", "T", ("w2",)))
_SPARSE = (("moe_%d/gate", "T", ("wr",)), ("moe_%d/w_up", "cat", ("e1", "e3")),
           ("moe_%d/w_down", "", ("e2",)),
           ("moe_%d/shared_up", "cat", ("s1", "s3")),
           ("moe_%d/shared_down", "", ("s2",)))


def leaf_index(sz):
    """``{program parameter: (how, reference leaves, layer or None)}``."""
    out = {"tok_embedding/table": ("", ("tok_emb",), None),
           "ln_final/scale": ("", ("g_final",), None),
           "lm_head/kernel": ("T", ("head",), None)}
    for i, layer in enumerate(sz["layers"]):
        for pat, how, leaves in _ATTENTION + (
                _SPARSE if layer["mlp"] == "sparse" else _DENSE):
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
