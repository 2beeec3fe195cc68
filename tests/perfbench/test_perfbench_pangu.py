"""The openPangu-Ultra-MoE family (ISSUE 41) and its cell,
``pangu-ultra-moe-718b.serve.closed-32-longdoc``.

The cell is listed in BENCHMARK.json as new entries at the end of their
lists; its tiny preset lies under ``data/tiny/`` and its record of two sets
of six under ``data/serve_spreads/``, so ``test_perfbench_contract.py`` holds
it to the serve bounds and runs its preset traced and untraced beside the
others.  Here: the listing against ISSUE 41's lists, the configuration file
against the published one, the family's operation count, the tiny cell against
the family's reference, and the float8 control coming out not correct.

Then the program against the plain reference on the CPU in float32: the
graph's ``forward`` (logits), prefill in chunks then token steps through the
paged latent cache (logits of every served token), and the same comparison
FAILING when the program computes in bfloat16."""

import os

import numpy as np
import pytest

import pb_control
import pb_tiny

CELL = "pangu-ultra-moe-718b.serve.closed-32-longdoc"
CONFIG = "pangu-ultra-moe-718b"
PRESET = "tiny-pangu.serve"
SIBLING = "laguna-xs2.serve.closed-128-code"
NEW = {"latent_decode_roofline", "latent_absorb_share"}
# the published config.json's numbers (the catalog's row), key for key
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pbpangu"))


def _cell(tree, name):
    from perfbench.harness import cells

    return cells.load(tree, name)


def test_the_cell_is_listed_last_and_on_the_lists_issue_41_names():
    """The LAST configuration and cell, on one chip, appended to every list
    that names the laguna cell but ``window_decode_roofline``'s (no window
    here), with its two readers listed for it alone at the end."""
    bench = pb_tiny._json(os.path.join(pb_tiny.REPO, "BENCHMARK.json"))
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config=CONFIG,
        traffic="closed-32-longdoc", chips=1)
    assert len(bench["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    metrics = bench["end_to_end"] + bench["per_layer"]
    on = {m["name"] for m in metrics if CELL in m.get("workloads", ())}
    assert NEW < on and "window_decode_roofline" not in on
    assert on - NEW == {m["name"] for m in metrics if SIBLING in m.get(
        "workloads", ())} - {"window_decode_roofline"}
    for m in metrics:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    for m in bench["per_layer"][-2:]:
        assert m["name"] in NEW and m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            pb_tiny.REPO, "perfbench", "layer_metrics", m["name"] + ".py"))
    cell = _cell(pb_tiny.REPO, CELL)
    assert cell.config["family"] == "pangu_moe"
    assert set(cell.doc["limits"]) == {"served_gap_mean",
                                       "served_gap_widest"}
    tr = cell.traffic
    assert (tr["slots"], tr["clients"], tr["size_grid"], tr["grid_seed"]) \
        == (32, 32, 256, 7)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 4096,
                                "sigma": 0.5, "min": 2048, "max": 12288}
    assert tr["new_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert (tr["warm_s"], tr["trace_after_s"], tr["trace_s"],
            tr["compared_requests"], tr["drain_s"]) == (30, 3, 3, 16, 60)
    args = [str(a) for a in tr["program_args"]]
    assert args[args.index("--serve-prefill-chunk") + 1] == "512"
    assert args[args.index("--serve-gen-max-seq") + 1] == "12800"


def test_the_configuration_keeps_every_published_width():
    """Every number of the published config under its own key; what differs
    is in ``reduced``, is a count and never a width, and the published count
    stands beside it; 4.92 B parameters by the family's own leaf shapes."""
    cell = _cell(pb_tiny.REPO, CELL)
    cfg = cell.config
    differs = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert {k: cfg["published"][k] for k in differs} == {
        k: PUBLISHED[k] for k in differs}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19200)
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    for said in ("16 chips", "data-parallel attention", "8 ways"):
        assert said in cfg["deployment"], said
    fam = cell.module("families", cfg["family"])
    ref = cell.module("reference", fam.REFERENCE)
    sz = fam.sizes(cfg)
    assert (sz["experts"], sz["router_experts"], sz["vocab"]) == (16, 256,
                                                                  19200)
    total = sum(int(np.prod(ref.leaf_shape(sz, n))) for n in ref.TOP) + sum(
        int(np.prod(ref.leaf_shape(sz, n, i)))
        for i in range(len(sz["layers"])) for n in ref.layer_leaves(sz, i))
    assert total == 4_919_139_840
    assert set(fam.leaf_index(sz)) >= {"attention_4/wkv_b", "moe_4/gate",
                                       "ln_ffn_out_0/scale"}


def test_the_familys_count_of_operations_and_bytes():
    """``perfbench/flops/pangu_moe.py`` at the published sizes: the token
    step's latent core needs 1 152 B and 2 x 128 x 1 088 operations a cached
    position a layer (on a v5e's ridge: 242 operations a byte), a held
    expert's weights are 3 x 7 680 x 2 048 values, and a token takes half a
    routed expert here at the expectation."""
    cell = _cell(pb_tiny.REPO, CELL)
    fam = cell.module("families", cell.config["family"])
    flops = cell.module("flops", fam.FLOPS)
    sz = fam.sizes(cell.config)
    nbytes, ops = flops.latent_decode_need(sz, 1000, 2)
    assert nbytes == 1000 * 5 * 1152 and ops == 1000 * 5 * 2 * 128 * 1088
    assert 240 < ops / nbytes < 243
    assert flops.moe_decode_bytes(sz, 3, 2) == 3 * 3 * 7680 * 2048 * 2
    assert flops.sparse_layers(sz) == 4
    one = flops.serve_flops(sz, 1, 0, []) - flops.serve_flops(sz, 0, 0, [])
    held = dict(sz, experts=32)
    more = flops.serve_flops(held, 1, 0, []) - flops.serve_flops(held, 0, 0,
                                                                 [])
    # 16 more held experts: half a routed expert more a token a sparse layer
    assert more - one == 4 * 0.5 * 2 * 3 * 7680 * 2048
    # a prompt's pair of query and key costs the expanded core's 320 a head
    p2 = flops.serve_flops(sz, 0, 0, [2]) - 2 * flops.serve_flops(sz, 0, 0,
                                                                  [1])
    assert p2 + 2 * 7680 * 19200 == 5 * 2 * 128 * 320


@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_the_tiny_cell_agrees_with_the_reference(tree, seed, capsys):
    """The tiny cell as the harness runs it (bfloat16, its own limits)."""
    result = pb_tiny.run(tree, PRESET, seed=seed, seconds=0.6)
    assert result["correct"] is True, capsys.readouterr().out
    assert result["attempted"] > 0 and result["failed"] == 0
    for name, value, limit in result["compared"]:
        assert value <= limit, name


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_comes_out_not_correct(tree, seed):
    numbers = pb_control.control_numbers(_cell(tree, PRESET), seed)
    assert any(not value <= limit for _, value, limit in numbers), numbers


def test_a_traced_run_reads_what_needs_no_device_trace(tree, capsys):
    """On the CPU a traced run reports every reader listed for the cell whose
    source is not the device trace and none whose source is (the two new
    readers among them: they return nothing without a trace and do not
    raise); the ``decode_step`` spans carry what the sparse layer counted of
    the experts HELD."""
    from perfbench.harness import cells

    cell = cells.load(tree, PRESET)
    assert NEW <= {m["name"] for m in cell.per_layer}
    result = pb_tiny.run(tree, PRESET, seed=2**31 + 41, seconds=1.0, trace=1)
    assert result["correct"] is True, capsys.readouterr().out
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert want and set(result["metrics"]) == want


def test_the_tiny_cells_owner_tables_carry_the_latent_parts(tree, capsys):
    """What ``test_perfbench_serve_owners.py`` asks of the tiny serve cells,
    asked of this one here (that file pins the NUMBER of tiny serve presets:
    PERF.md section 7): a table for every program the engine's warm-up
    builds, no chunk bucket past the cell's chunk of 8, and in each the parts
    the two new readers sum, ``mla_core`` and ``mla_absorb``."""
    from perfbench.harness import serve_owners

    got = serve_owners.tables(_cell(tree, PRESET))
    assert "owner tables of" in capsys.readouterr().out
    chunks = [n for n in got if serve_owners.program_kind(n) == "chunk"]
    assert sorted(chunks) == ["jit_prefill_2", "jit_prefill_4",
                              "jit_prefill_8"]
    assert set(got) == {*chunks, "jit_decode", "jit_splice_tokens"}
    for name in (*chunks, "jit_decode"):
        owners = set(got[name].values())
        assert ("sample", None) in owners, name
        for part in ("mla_core", "mla_absorb"):
            assert any(o and o.startswith("attention_") and p == part
                       for o, p in owners), (name, part)
        assert any(p == "moe_experts" for _, p in owners), name


# ---------------------------------------------------------------------------
# the program against its reference in float32
# ---------------------------------------------------------------------------
def _program(tree, compute_dtype):
    """The tiny cell's graph built by its family through the normal path,
    computing in ``compute_dtype`` (the weights are the bfloat16-rounded
    ones on both sides either way), with the reference's weights of seed 11
    installed."""
    cell = _cell(tree, PRESET)
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    config = dict(cell.config, run=dict(cell.config["run"],
                                        compute_dtype=compute_dtype))
    sz = fam.sizes(config)
    model = fam.build_serve(config, cell.traffic)
    fam.install(model, sz, ref.init_params(sz, 11))
    return model, ref, sz


@pytest.fixture(scope="module")
def pangu(tree):
    return _program(tree, "float32")


def _forward_gap(model, ref, sz):
    import jax

    tok = np.random.default_rng(1).integers(
        1, sz["vocab"], (2, sz["positions"])).astype(np.int32)
    got = np.log(np.asarray(model.predict([tok], batch_size=2), np.float64))
    want = np.asarray(jax.nn.log_softmax(ref.lm_logits(
        ref.init_params(sz, 11), tok, sz), axis=-1), np.float64)
    assert got.shape == want.shape == (2, sz["positions"], sz["vocab"])
    return float(np.abs(got - want).max())


def test_the_graphs_forward_agrees_with_its_reference(pangu, tree):
    """Log-probabilities of the graph's ``forward`` (``predict``: the
    expanded form, no cache) against the reference's over the whole held
    vocabulary at 2 x 96 positions.  Tolerance 2e-4: float32 on both sides
    with the same bfloat16-rounded weights, the program's products the
    backend's default float32 in another order than the reference's
    ``Precision.HIGHEST`` ones.  The SAME graph computing in bfloat16 is a
    hundred times over it: the comparison would catch the lower precision."""
    import jax.numpy as jnp

    model, ref, sz = pangu
    assert _forward_gap(model, ref, sz) <= 2e-4
    assert _forward_gap(*_program(tree, "bfloat16")) > 2e-2
    # causal: changing a token changes no earlier position's logits
    tok = np.random.default_rng(2).integers(1, sz["vocab"], (1, 64)).astype(
        np.int32)
    other = tok.copy()
    other[:, 40:] = (other[:, 40:] + 5) % sz["vocab"]
    a = ref.lm_logits(ref.init_params(sz, 11), tok, sz)
    b = ref.lm_logits(ref.init_params(sz, 11), other, sz)
    assert float(jnp.max(jnp.abs(a[:, :40] - b[:, :40]))) == 0.0
    assert float(jnp.max(jnp.abs(a[:, 40:] - b[:, 40:]))) > 0.0


def _served(model, sz):
    import flexflow_tpu as ff
    from flexflow_tpu import fflogger

    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, sz["vocab"], n).astype(np.int32)
               for n in (5, 13, 21, 40)]
    with fflogger.silenced("serve"):
        with ff.GenerationEngine(model, slots=2, max_new_tokens=24) as eng:
            streams = [eng.submit(p, max_new_tokens=24) for p in prompts]
            served = [[int(t) for t in s.result(timeout=300)]
                      for s in streams]
    return list(zip(prompts, served))


def test_served_tokens_are_the_references_best_at_every_position(pangu, tree):
    """Prefill in chunks of 8 (the expanded, blocked core over the latent
    pages) then token steps (the ABSORBED core) through the engine's cache,
    against the reference's full forward over prompt + served tokens: prompts
    inside one page, across a page's edge and past several chunks (40 + 24
    positions).  In float32 the served token's reference logit lies within
    2e-4 of the reference's best everywhere (it IS the best unless two
    logits tie within the arithmetic's noise).  Served in bfloat16, the
    same comparison reads a hundred times that: tight enough to catch it."""
    model, ref, sz = pangu
    gaps = ref.served_gaps(sz, 11, _served(model, sz), "float32")
    for g in gaps:
        assert len(g["served"]) == 24
        assert float(np.max(g["served"])) <= 2e-4, g["served"]
    low = _program(tree, "bfloat16")
    widest = max(float(np.max(g["served"])) for g in ref.served_gaps(
        sz, 11, _served(low[0], sz), "float32"))
    assert widest > 2e-3, widest
