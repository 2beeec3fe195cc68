"""The Keye-VL-2.0 family's decoder (ISSUE 44) and its cell,
``keye-vl-2.0-30b-a3b.serve.closed-24-longctx``.

The cell is listed in BENCHMARK.json as new entries at the end of their
lists; its tiny preset lies under ``data/tiny/`` and its record of two sets
of six under ``data/serve_spreads/``, so ``test_perfbench_contract.py`` holds
it to the serve bounds and runs its preset traced and untraced beside the
others.  Here, everything BY NAME, nothing by position or count: the listing
against ISSUE 44's lists, the configuration file against the published one, the
family's operation and byte counts by hand, the reference's own pieces (the
three-section rotary, the choice by a full sort), the tiny cell against the
family's reference, and the float8 control coming out not correct.

Then the program against the plain reference on the CPU in float32: the
graph's ``forward`` (logits), prefill in chunks then token steps through the
three-leaf cache at histories past ``topk`` (logits of every served token),
and the same comparison FAILING when the program computes in bfloat16."""

import os

import numpy as np
import pytest

import pb_control
import pb_tiny

CELL = "keye-vl-2.0-30b-a3b.serve.closed-24-longctx"
CONFIG = "keye-vl-2.0-30b-a3b"
PRESET = "tiny-sparse.serve"
SIBLING = "pangu-ultra-moe-718b.serve.closed-32-longdoc"
NEW = {"sparse_decode_roofline", "sparse_select_share"}
LATENT = {"latent_decode_roofline", "latent_absorb_share"}
# the published config.json's numbers (the catalog's row), key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
PUBLISHED_GROUPS = {
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "mlp_only_layers": [], "sliding_window": None}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pbkeye"))


def _cell(tree, name):
    from perfbench.harness import cells

    return cells.load(tree, name)


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_cell_is_on_the_lists_issue_44_names():
    """By name: the configuration, the cell on one chip with ISSUE 44's
    traffic, on every list that names the pangu cell but its two latent
    readers', and its own two readers listed for it alone."""
    bench = pb_tiny._json(os.path.join(pb_tiny.REPO, "BENCHMARK.json"))
    config = _named(bench["configs"], CONFIG)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    entry = _named(bench["workloads"], CELL)
    assert entry == dict(entry, config=CONFIG, traffic="closed-24-longctx",
                         chips=1)
    metrics = bench["end_to_end"] + bench["per_layer"]
    on = {m["name"] for m in metrics if CELL in m.get("workloads", ())}
    assert NEW < on and not LATENT & on
    assert on - NEW == {m["name"] for m in metrics if SIBLING in m.get(
        "workloads", ())} - LATENT
    assert {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "serve_mfu",
            "moe_share", "moe_decode_roofline", "attention_share.serve",
            "serve_unowned_share", "chunk_device_ms",
            "decode_device_ms"} < on
    for name in NEW:
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
        assert m["source"] == "device_trace"
        assert os.path.isfile(os.path.join(
            pb_tiny.REPO, "perfbench", "layer_metrics", name + ".py"))
    assert _named(bench["per_layer"], "sparse_decode_roofline")[
        "layer"] == "L4 kernels"
    cell = _cell(pb_tiny.REPO, CELL)
    assert cell.config["family"] == "keye_vl"
    assert set(cell.doc["limits"]) == {"served_gap_mean",
                                       "served_gap_widest"}
    tr = cell.traffic
    assert tr["kind"] == "serve_closed"
    assert (tr["slots"], tr["clients"], tr["size_grid"], tr["grid_seed"]) \
        == (24, 24, 256, 7)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                "sigma": 0.5, "min": 4096, "max": 24576}
    assert tr["new_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert (tr["warm_s"], tr["trace_after_s"], tr["trace_s"],
            tr["compared_requests"], tr["drain_s"]) == (30, 3, 3, 16, 60)
    args = [str(a) for a in tr["program_args"]]
    assert args[args.index("--serve-prefill-chunk") + 1] == "512"
    assert args[args.index("--serve-gen-max-seq") + 1] == "25088"
    # every prompt is past topk, and the longest request fits a slot
    assert tr["prompt_len"]["min"] > cell.config["sa_config"]["topk"]
    assert tr["prompt_len"]["max"] + tr["new_tokens"]["max"] \
        < cell.config["run"]["max_seq"]


def test_the_configuration_keeps_every_published_width():
    """Every number and every group of the published config under its own
    key; the depth alone differs, is in ``reduced`` with the published count
    beside it; ``assumed`` lists the five items and what is not built; 3.12 B
    parameters by the family's own leaf shapes."""
    cell = _cell(pb_tiny.REPO, CELL)
    cfg = cell.config
    differs = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 4
    for key, group in PUBLISHED_GROUPS.items():
        assert cfg[key] == group, key
    assert (cfg["hidden_act"], cfg["model_type"]) == ("silu", "KeyeVL2")
    for item in ("qk norm", "rope", "indexer", "selection", "chunk sizes",
                 "max_seq", "weights", "kv", "not built"):
        assert item in cfg["assumed"], item
    assert "vision tower" in cfg["assumed"]["not built"]
    assert "three distinct position streams" in cfg["assumed"]["not built"]
    for said in ("12 pipeline stages", "WHOLE", "stage 0"):
        assert said in cfg["deployment"], said
    assert cfg["run"] == dict(cfg["run"], compute_dtype="bfloat16",
                              param_dtype="bfloat16", kv_page_tokens=16,
                              max_seq=25088)
    fam = cell.module("families", cfg["family"])
    ref = cell.module("reference", fam.REFERENCE)
    sz = fam.sizes(cfg)
    assert (sz["experts"], sz["k"], sz["expert_ff"], sz["vocab"]) == (
        128, 8, 768, 151936)
    assert (sz["index_heads"], sz["index_dim"], sz["topk"]) == (16, 64, 2048)
    layer = sum(int(np.prod(ref.leaf_shape(sz, n, 0)))
                for n in ref.layer_leaves(sz, 0))
    assert layer == 625_381_760
    indexer = sum(int(np.prod(ref.leaf_shape(sz, n, 0)))
                  for n in ("wiq", "wik", "wiw", "gik", "bik"))
    assert indexer == 2_261_120
    total = 4 * layer + sum(int(np.prod(ref.leaf_shape(sz, n)))
                            for n in ref.TOP)
    assert total == 3_123_858_944
    assert set(fam.leaf_index(sz)) >= {"attention_3/wiq", "attention_0/ik_bias",
                                       "attention_2/k_norm", "moe_3/gate"}
    assert not any("shared" in name for name in fam.leaf_index(sz))


def test_the_familys_count_of_operations_and_bytes():
    """``perfbench/flops/keye_vl.py`` at the published sizes, by hand: a
    decoded token's attention needs 128 B of indexer key a live position and
    2 048 rows of 2 048 B, a layer; the indexer costs 2 x 16 x 64 operations
    a pair, the heads 2 x 2 x 32 x 128 over the chosen keys only; an expert's
    weights are 3 x 2 048 x 768 values."""
    cell = _cell(pb_tiny.REPO, CELL)
    fam = cell.module("families", cell.config["family"])
    flops = cell.module("flops", fam.FLOPS)
    sz = fam.sizes(cell.config)
    assert flops.sparse_decode_bytes(sz, 10_000, 1, 2) == 4 * (
        10_000 * 128 + 2048 * 2048)
    # a history under topk reads what is live, never more
    assert flops.sparse_decode_bytes(sz, 1_000, 1, 2) == 4 * 1_000 * (
        128 + 2048)
    assert flops.moe_decode_bytes(sz, 3, 2) == 3 * 3 * 2048 * 768 * 2
    assert flops.sparse_layers(sz) == 4
    per_token = 4 * (2 * 2048 * (32 * 128 + 2 * 4 * 128) + 2 * 32 * 128 * 2048
                     + 2 * 2048 * (16 * 64 + 64 + 16)
                     + 2 * 2048 * 128 + 2 * 3 * 2048 * 8 * 768)
    head = 2 * 2048 * 151936
    assert flops.serve_flops(sz, 1, 0, []) == per_token + head
    index, core = 4 * 2 * 16 * 64, 4 * 2 * 2 * 32 * 128
    assert flops.serve_flops(sz, 1, 10_000, []) - flops.serve_flops(
        sz, 1, 0, []) == index * 10_000 + core * 2048
    # a prompt of 3 000: every pair scored, rows past 2 048 attend over 2 048
    assert flops.serve_flops(sz, 0, 0, [3000]) == (
        3000 * per_token + head + index * (3000 * 3001 // 2)
        + core * (2048 * 2049 // 2 + 952 * 2048))


def test_the_three_section_rotary_is_the_plain_one_at_equal_streams():
    from perfbench.reference import keye_vl as ref

    rng = np.random.default_rng(0)
    x = rng.normal(size=(9, 3, 16)).astype(np.float32)
    pos = np.arange(9) * 7
    plain = np.asarray(ref.rope(x, pos, 1e7))
    three = np.asarray(ref.rope3(x, np.stack([pos, pos, pos]), 1e7,
                                 [2, 3, 3]))
    assert (plain == three).all()
    # and each run of frequencies turns by its OWN stream
    moved = np.asarray(ref.rope3(x, np.stack([pos, pos + 5, pos]), 1e7,
                                 [2, 3, 3]))
    same = np.isclose(moved, plain, atol=1e-6).all(axis=(0, 1))
    assert same.tolist() == [True] * 2 + [False] * 3 + [True] * 3 \
        + [True] * 2 + [False] * 3 + [True] * 3


def test_the_references_choice_is_a_full_sorts():
    from perfbench.reference import keye_vl as ref

    inf = np.inf
    x = np.asarray([[3.0, 1.0, 5.0, 0.5, 1.0, 1.0, 4.0, 1.0, -inf],
                    [2.0, -inf, -inf, -inf, -inf, -inf, -inf, -inf, -inf]],
                   np.float32)
    keep = np.asarray(ref.chosen(x, 5))
    assert keep[0].tolist() == [True, True, True, False, True, False, True,
                                False, False]
    assert keep[1].tolist() == [True] + [False] * 8
    assert np.asarray(ref.chosen(x[:, :4], 5)).tolist() == [
        [True] * 4, [True, False, False, False]]


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
    raise)."""
    from perfbench.harness import cells

    cell = cells.load(tree, PRESET)
    assert NEW <= {m["name"] for m in cell.per_layer}
    result = pb_tiny.run(tree, PRESET, seed=2**31 + 44, seconds=1.0, trace=1)
    assert result["correct"] is True, capsys.readouterr().out
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert want and set(result["metrics"]) == want


def test_the_new_readers_sum_the_three_parts():
    """The two readers on a made-up owner table (no trace, no chip):
    ``sparse_decode_roofline`` is the family's bytes over the bandwidth over
    the token steps' three parts' seconds; ``sparse_select_share`` the index
    and select seconds of chunks and steps over the busy seconds; a loop's
    own instruction, which spans its body's events, is left out of both."""
    import types

    cell = _cell(pb_tiny.REPO, CELL)
    fam = cell.module("families", cell.config["family"])
    sz = fam.sizes(cell.config)
    flops = cell.module("flops", fam.FLOPS)
    seconds = {("token", "attention", "dsa_index"): 0.010,
               ("token", "attention", "dsa_select"): 0.002,
               ("token", "attention", "dsa_core"): 0.008,
               ("token", "attention", None): 0.5,
               ("chunk", "attention", "dsa_index"): 0.030,
               ("chunk", "attention", "dsa_select"): 0.050,
               ("chunk", "attention", "dsa_core"): 0.7,
               ("chunk", "moe", "dsa_index"): 9.0}
    by_kind = {"while": {"chunk:attention.dsa_select": 0.020,
                         "chunk:attention": 0.5,
                         "token:attention.dsa_core": 0.001},
               "conditional": {"chunk:attention.dsa_select": 0.002},
               "fusion": {"chunk:attention.dsa_select": 0.028}}
    obs = types.SimpleNamespace(
        cell=cell, sizes=sz, flops=flops, trace={}, window=(0, 1),
        counters={"traced_work": {"decode_tokens": 1000,
                                  "live_positions": 9_000_000,
                                  "prompt_lens": []}},
        peaks={"hbm_bytes_per_s": 819e9},
        _serve_owners={"seconds": seconds, "by_kind": by_kind},
        xtrace=types.SimpleNamespace(busy_seconds=lambda doc, win: 2.0))
    need = 4 * (9_000_000 * 128 + 1000 * 2048 * 2048)
    got = cell.module("layer_metrics", "sparse_decode_roofline").read(obs)
    assert got == pytest.approx(100.0 * need / 819e9 / 0.019)
    got = cell.module("layer_metrics", "sparse_select_share").read(obs)
    assert got == pytest.approx(100.0 * 0.070 / 2.0)
    # a program without the scopes: nothing to read, no error
    obs._serve_owners = {"seconds": {("token", "attention", None): 1.0},
                         "by_kind": {}}
    for name in NEW:
        assert cell.module("layer_metrics", name).read(obs) is None


def test_the_tiny_cells_owner_tables_carry_the_three_parts(tree, capsys):
    """What ``test_perfbench_serve_owners.py`` asks of the tiny serve cells,
    asked of this one here (that file pins the NUMBER of tiny serve presets:
    PERF.md section 7): a table for every program the engine's warm-up
    builds, no chunk bucket past the cell's chunk of 8, and in each the parts
    the two new readers sum."""
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
        for part in ("dsa_index", "dsa_select", "dsa_core"):
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
def keye(tree):
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


def test_the_graphs_forward_agrees_with_its_reference(keye, tree):
    """Log-probabilities of the graph's ``forward`` (``predict``: the dense
    core under a mask from the chosen sets, no cache) against the reference's
    over the whole vocabulary at 2 x 96 positions, 88 of them past ``topk``
    8.  Tolerance 2e-4: float32 on both sides with the same bfloat16-rounded
    weights, the program's products the backend's default float32 in another
    order than the reference's ``Precision.HIGHEST`` ones (a pair of index
    scores that swapped rank at the threshold would read far over it).  The
    SAME graph computing in bfloat16 is a hundred times over it: the
    comparison would catch the lower precision."""
    import jax.numpy as jnp

    model, ref, sz = keye
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


def test_served_tokens_are_the_references_best_at_every_position(keye, tree):
    """Prefill in chunks of 8 (the indexer's scores, the choice and the core
    over the paged view) then token steps through the engine's three leaves,
    against the reference's full forward over prompt + served tokens: a prompt
    under ``topk`` 8 that grows past it while decoding, prompts across a
    page's edge and past several chunks (40 + 24 positions, 8 chosen of up to
    64).  In float32 the served token's reference logit lies within 2e-4 of
    the reference's best everywhere (it IS the best unless two logits tie
    within the arithmetic's noise).  Served in bfloat16, the same comparison
    reads a hundred times that: tight enough to catch it."""
    model, ref, sz = keye
    gaps = ref.served_gaps(sz, 11, _served(model, sz), "float32")
    for g in gaps:
        assert len(g["served"]) == 24
        assert float(np.max(g["served"])) <= 2e-4, g["served"]
    low = _program(tree, "bfloat16")
    widest = max(float(np.max(g["served"])) for g in ref.served_gaps(
        sz, 11, _served(low[0], sz), "float32"))
    assert widest > 2e-3, widest
