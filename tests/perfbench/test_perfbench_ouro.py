"""The Ouro family (ISSUE 49: a stack every token passes through several times
with the same weights) and its cell, ``ouro-2.6b.serve.closed-12-reason``.

The cell is listed in BENCHMARK.json as new entries at the end of their lists;
its tiny preset lies under ``data/tiny/`` and its record of two sets of six
under ``data/serve_spreads/``.  Here, everything BY NAME, nothing by position
or count: the listing against ISSUE 49's lists, the configuration file against
the published one, the family's counts of parameters, bytes and operations by
hand, the tiny cell against the family's reference, the float8 control coming
out not correct, the two new readers.

Then the program against the plain reference on the CPU in float32: the
graph's ``forward`` (logits, all passes), prefill then token steps through the
cache by call site (every served token), and the same comparisons FAILING when
the program computes in bfloat16."""

import os
import types

import numpy as np
import pytest

import pb_control
import pb_tiny

CELL = "ouro-2.6b.serve.closed-12-reason"
CONFIG = "ouro-2.6b"
PRESET = "tiny-ouro.serve"
NEW = {"loop_stream_roofline": ("%", "device_trace", "L1 graph + compile",
                                "itl_p95_ms"),
       "loop_passes_per_token": ("count", "program_counter", "L5 serving",
                                 "serve_tokens_per_s")}
# ISSUE 49's lists, less ``ttft_p95_ms`` and the six per-layer metrics that
# move it: the cell's runs on the chip spread that percentile by more than the
# bound that stands allows (PERF.md section 2), and a per-layer metric lists
# only cells that report the end-to-end metric it moves
LISTED = {"serve_tokens_per_s", "itl_p95_ms", "compile_s", "cache_misses",
          "decode_step_ms", "slot_occupancy", "device_idle_share.serve",
          "step_host_ms", "decode_device_ms", "serve_mfu",
          "serve_unowned_share", "attention_share.serve",
          "paged_decode_roofline"}
NOT_HELD = {"ttft_p95_ms", "prefill_ms", "ttft_p50_ms", "queue_ms",
            "prefill_wait_ms", "prefill_exec_ms", "chunk_device_ms"}
# the published config.json's keys (the catalog's row), key for key
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pbouro"))


def _cell(tree, name):
    from perfbench.harness import cells

    return cells.load(tree, name)


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_cell_is_on_the_lists_issue_49_names():
    """By name: the configuration with nothing reduced, the cell on one chip
    with ISSUE 49's traffic, on the lists the issue names but those of the
    time to a first token, and its own two readers listed for it alone."""
    bench = pb_tiny._json(os.path.join(pb_tiny.REPO, "BENCHMARK.json"))
    config = _named(bench["configs"], CONFIG)
    assert config["reduced"] == []
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    entry = _named(bench["workloads"], CELL)
    assert entry == dict(entry, config=CONFIG, traffic="closed-12-reason",
                         chips=1)
    metrics = bench["end_to_end"] + bench["per_layer"]
    on = {m["name"] for m in metrics if CELL in m.get("workloads", ())}
    assert on == LISTED | set(NEW) and not on & NOT_HELD
    moved = {m["name"] for m in bench["per_layer"]
             if m["moves"] == "ttft_p95_ms"} | {"ttft_p95_ms"}
    assert moved == NOT_HELD
    for name, (unit, source, layer, moves) in NEW.items():
        m = _named(bench["per_layer"], name)
        assert m["workloads"] == [CELL]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, moves, "higher")
        assert os.path.isfile(os.path.join(
            pb_tiny.REPO, "perfbench", "layer_metrics", name + ".py"))
    cell = _cell(pb_tiny.REPO, CELL)
    assert cell.config["family"] == "ouro"
    assert set(cell.doc["limits"]) == {"served_gap_mean",
                                       "served_gap_widest"}
    tr = cell.traffic
    assert tr["kind"] == "serve_closed"
    assert (tr["slots"], tr["clients"], tr["size_grid"], tr["grid_seed"]) \
        == (12, 12, 256, 7)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 96,
                                "sigma": 0.5, "min": 32, "max": 224}
    assert tr["new_tokens"] == {"dist": "uniform", "min": 96, "max": 208}
    assert (tr["warm_s"], tr["trace_after_s"], tr["trace_s"],
            tr["compared_requests"], tr["drain_s"]) == (30, 3, 3, 16, 60)
    args = [str(a) for a in tr["program_args"]]
    assert args[args.index("--serve-prefill-chunk") + 1] == "256"
    assert args[args.index("--serve-gen-max-seq") + 1] == "448"
    # every prompt is one chunk, outputs are longer than the median prompt,
    # and the longest request fits a slot
    assert tr["prompt_len"]["max"] <= 256
    assert tr["new_tokens"]["min"] >= tr["prompt_len"]["median"]
    assert tr["prompt_len"]["max"] + tr["new_tokens"]["max"] \
        < cell.config["run"]["max_seq"]


def test_the_configuration_keeps_every_published_key():
    """Every key of the published config under its own name and unchanged,
    ``reduced`` empty, ``published`` empty (nothing was cut), and everything
    the config does not say listed under ``assumed``."""
    config = _cell(pb_tiny.REPO, CELL).config
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == [] and config["published"] == {}
    assert config["run"] == {
        "compute_dtype": "bfloat16", "param_dtype": "bfloat16",
        "kv_dtype": "bfloat16", "kv_page_tokens": 16, "max_seq": 448}
    assert "no layer is divided" in config["deployment"]
    assert {"sandwich norms", "norm between passes", "exit gate",
            "cache per pass", "bias", "rope", "window", "max_seq",
            "weights"} <= set(config["assumed"])


def test_the_familys_counts_by_hand():
    """``perfbench/flops/ouro.py`` and the family's sizes at the published
    configuration, against numbers worked by hand: the parameters HELD
    (once, whatever the passes), a token's bytes over the 192 call sites, a
    token step's bytes, and the operations of a decoded token and a
    prompt."""
    cell = _cell(pb_tiny.REPO, CELL)
    fam = cell.module("families", "ouro")
    flops = cell.module("flops", fam.FLOPS)
    sz = fam.sizes(cell.config)
    assert (len(sz["layers"]), sz["passes"], sz["exit_threshold"]) \
        == (48, 4, 1.0)
    assert flops.call_sites(sz) == 192
    # a layer: 4 x 2048 x 2048 + 3 x 2048 x 5632 + 4 x 2048
    layer = 16_777_216 + 34_603_008 + 8_192
    assert flops.layer_params(sz) == layer == 51_388_416
    params = 48 * layer + 2 * 49_152 * 2_048 + 2_048 + 2_048 + 1
    assert flops.param_count(sz) == params == 2_667_974_657
    assert f"{params / 1e9:.3f} B parameters" in cell.config["size_note"]
    assert len(fam.leaf_index(sz)) == 48 * 11 + 5
    # 192 call sites x (K and V) x 16 heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(sz, 2) == 192 * 8_192 == 1_572_864
    assert flops.decode_kv_bytes(sz, 1_000, 2) == 1_572_864_000
    # a step: the 48 layers' weights 4 times, the head once; then the rows
    weights = (4 * 48 * layer + 2_048 * 49_152) * 2
    assert flops.token_step_bytes(sz, 3, 1_000, 2) \
        == 3 * weights + 1_572_864_000
    assert weights == 19_934_478_336
    # a decoded token: 2 operations a weight of the matrices a call site,
    # the head once, 2 x 2 x 16 x 128 a live position a call site
    per_site = 2 * (16_777_216 + 34_603_008)
    head = 2 * 2_048 * 49_152
    assert flops.serve_flops(sz, 1, 100, []) \
        == 192 * per_site + head + 192 * 8_192 * 100
    assert flops.serve_flops(sz, 0, 0, [10]) \
        == 10 * 192 * per_site + head + 192 * 8_192 * 55


def test_the_published_size_builds_through_the_normal_path():
    """The configuration through ``FFConfig.parse_args``, the builder and
    ``compile`` (no weight is made): 192 call sites of each kind of op,
    2 668 M values in ``model.parameters``, 1 572 864 B a token in the
    layout the engine allocates and the memory gates charge (48 entries of
    four regions each), 8.46 GB for 12 slots, every leaf pageable and
    nothing refused."""
    from flexflow_tpu.analysis.kv_memory import kv_page_plan
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    cell = _cell(pb_tiny.REPO, CELL)
    fam = cell.module("families", "ouro")
    model = fam.build_serve(cell.config, cell.traffic)
    names = [op.name for op in model.layers]
    for kind in ("attention", "ln_attn", "ffn_gate", "res_ffn"):
        assert sum(n.rsplit("_", 1)[0] == kind for n in names) == 192, kind
    assert sum(p.volume for p in model.parameters) == 2_667_974_657
    assert model.loop == (1, 48 * 11 + 1, 4)
    plan = kv_page_plan(model.layers, {"n": 1}, 12, 448, page_size=16)
    assert plan["page_bytes"] / 16 == 1_572_864
    assert plan["num_pages"] == 12 * 28
    assert plan["pool_bytes"] == 12 * 448 * 1_572_864 == 8_455_716_864
    dec = GraphDecoder(model, 12, 448, prefill_chunk=256)
    assert dec.pageable and not dec.windowed
    assert all(dec.refusal(what) is None for what in (
        "prefix reuse", "speculation", "migration"))
    assert len([n for n, e in dec.layout.items() if e["kind"] == "kv"]) == 48
    assert dec.layout["attention_0"]["shapes"]["k"] == (4 * 336, 16, 2048)
    assert dec.buckets == (2, 4, 8, 16, 32, 64, 128, 256)


def test_the_references_exit_rule():
    """The reference's gate on made-up states: the distribution sums to 1,
    the chosen pass is the first whose cumulative mass reaches the threshold
    and the last where none before it does (threshold 1, the published
    one)."""
    import jax.numpy as jnp

    cell = _cell(pb_tiny.REPO, CELL)
    ref = cell.module("reference", "ouro")
    # lam = sigmoid(h . 1): passes 0.5, 0.88, 0.27 for three states
    states = [jnp.asarray([[v]], jnp.float32) for v in (0.0, 2.0, -1.0)]
    w, b = jnp.ones((1, 1), jnp.float32), jnp.zeros((1,), jnp.float32)
    p, exit_ = ref.exit_distribution(states, w, b, 0.6)
    lam = 1 / (1 + np.exp(-np.asarray([0.0, 2.0])))
    want = [lam[0], (1 - lam[0]) * lam[1], (1 - lam[0]) * (1 - lam[1])]
    np.testing.assert_allclose(np.asarray(p)[0], want, rtol=1e-6)
    assert abs(float(jnp.sum(p)) - 1.0) < 1e-6
    assert int(exit_[0]) == 1            # 0.5 < 0.6 <= 0.5 + 0.44
    assert int(ref.exit_distribution(states, w, b, 0.5)[1][0]) == 0
    assert int(ref.exit_distribution(states, w, b, 1.0)[1][0]) == 2


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
    source is not the device trace and none whose source is;
    ``loop_passes_per_token`` reads the preset's three passes off the
    ``decode_step`` spans."""
    cell = _cell(tree, PRESET)
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    result = pb_tiny.run(tree, PRESET, seed=2**31 + 49, seconds=1.0, trace=1)
    assert result["correct"] is True, capsys.readouterr().out
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert want and set(result["metrics"]) == want
    assert result["metrics"]["loop_passes_per_token"]["value"] == 3.0


def test_the_new_readers_on_made_up_numbers():
    """``loop_stream_roofline`` on a made-up trace of two whole token steps
    (a third one cut by the window's edge is left out), by hand; both
    readers return nothing, and do not raise, where the program has no such
    span, counter or count (the parent's)."""
    cell = _cell(pb_tiny.REPO, CELL)
    fam = cell.module("families", "ouro")
    flops = cell.module("flops", fam.FLOPS)
    sz = fam.sizes(cell.config)
    stream = cell.module("layer_metrics", "loop_stream_roofline")
    passes = cell.module("layer_metrics", "loop_passes_per_token")
    ms = 1_000_000
    trace = {"devices": {0: {"ops": [], "modules": [
        ("jit_decode(123)", 10 * ms, 30 * ms),
        ("jit_prefill_64(5)", 40 * ms, 20 * ms),
        ("jit_decode(123)", 60 * ms, 30 * ms),
        ("jit_decode(123)", 95 * ms, 30 * ms)]}}}
    obs = types.SimpleNamespace(
        cell=cell, sizes=sz, flops=flops, trace=trace,
        window=(0, 100 * ms), peaks={"hbm_bytes_per_s": 819e9},
        counters={"traced_work": {"decode_tokens": 24,
                                  "live_positions": 4_000,
                                  "prompt_lens": []}}, spans=[])
    need = 2 * 19_934_478_336 + 4_000 * 1_572_864
    assert stream.read(obs) == pytest.approx(
        100.0 * need / 819e9 / 0.060)
    assert passes.read(obs) is None
    obs.spans = [{"name": "decode_step", "args": {"loop_tokens": t,
                                                  "loop_passes": p}}
                 for t, p in ((100, 400), (112, 448), (124, 496))]
    assert passes.read(obs) == 4.0
    # a program without the count, a trace without a whole token step
    obs.flops = types.SimpleNamespace(DECODE_PROGRAM="jit_decode(")
    assert stream.read(obs) is None
    obs.flops, obs.window = flops, (0, 5 * ms)
    assert stream.read(obs) is None
    obs.trace = None
    assert stream.read(obs) is None


def test_the_tiny_cells_owner_tables(tree, capsys):
    """What ``test_perfbench_serve_owners.py`` asks of the tiny serve cells,
    asked of this one here (that file pins the NUMBER of tiny serve presets:
    PERF.md section 7): a table for every program the engine's warm-up
    builds and no chunk bucket past the cell's chunk of 64; in each, the
    layer list's ops own instructions under pass 1's names (the programs run
    the passes as one loop), the final norm, the gate and the head under
    their own, the loop's own arithmetic under ``step_io``; every matrix
    product of the token step has an owner (what that leaves to nobody in
    device seconds is the chip's reading, ``serve_unowned_share``: on the
    CPU the unowned instructions are constants, broadcasts and the inner
    computations of reductions and scatters, as in the sibling presets)."""
    from perfbench.harness import serve_owners

    got = serve_owners.tables(_cell(tree, PRESET))
    assert "owner tables of" in capsys.readouterr().out
    chunks = [n for n in got if serve_owners.program_kind(n) == "chunk"]
    assert sorted(chunks, key=lambda n: int(n.rsplit("_", 1)[1])) == [
        f"jit_prefill_{b}" for b in (2, 4, 8, 16, 32, 64)]
    assert set(got) == {*chunks, "jit_decode", "jit_splice_tokens"}
    for name in (*chunks, "jit_decode"):
        owners = {o for o, _ in got[name].values()}
        assert {"attention_0", "attention_1", "ffn_down_1", "ln_final",
                "exit_gate", "lm_head", "sample", "step_io"} <= owners, name
        assert not {"attention_2", "ln_final_1"} & owners, name
    step = got["jit_decode"]
    dots = [owner for name, (owner, _) in step.items()
            if name.partition(".")[0] == "dot"]
    assert len(dots) >= 2 * 7 + 2 and None not in dots
    assert sum(o is None for o, _ in step.values()) < len(step) / 3


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
def ouro(tree):
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


def test_the_graphs_forward_agrees_with_its_reference(ouro, tree):
    """Log-probabilities of the graph's ``forward`` (``predict``: every pass
    laid out, no cache) against the reference's over the whole vocabulary at
    2 x 96 positions.  Tolerance 2e-4: float32 on both sides with the same
    bfloat16-rounded weights, the program's products the backend's default
    float32 in another order than the reference's ``Precision.HIGHEST`` ones.
    The SAME graph computing in bfloat16 reads fifty times that (0.012): the
    comparison would catch the lower precision.  The installed graph holds
    each parameter once."""
    model, ref, sz = ouro
    assert len(model._params) == len(model.parameters) == 2 * 11 + 5
    assert _forward_gap(model, ref, sz) <= 2e-4
    assert _forward_gap(*_program(tree, "bfloat16")) > 5e-3


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
            stats = eng.stats()
    return list(zip(prompts, served)), stats


def test_served_tokens_are_the_references_best_at_every_position(ouro, tree):
    """Prefill then token steps through the engine's cache by call site
    (three passes' regions of two layers' leaves), against the reference's
    full forward over prompt + served tokens, all passes: prompts across a
    page's edge, two streams at once.  In float32 the served token's
    reference logit lies within 2e-4 of the reference's best everywhere.
    Served in bfloat16, the same comparison reads over ten times that: tight
    enough to catch it.  ``stats()`` carries the three counters."""
    model, ref, sz = ouro
    served, stats = _served(model, sz)
    gaps = ref.served_gaps(sz, 11, served, "float32")
    for g in gaps:
        assert len(g["served"]) == 24
        assert float(np.max(g["served"])) <= 2e-4, g["served"]
    # every prompt row and token step, and the warm-up's one row a bucket
    tokens = sum(len(p) + 24 - 1 for p, _ in served) + 6
    assert stats["loop"]["tokens"] == tokens
    assert stats["loop_passes"] == 3 * tokens
    assert stats["loop"]["exits_by_pass"] == [0, 0, tokens]
    assert abs(sum(stats["exit_mass_by_pass"]) - 1.0) < 1e-3
    # 2 layers x 3 passes x (K and V) x 4 heads x 16 x 4 bytes
    assert stats["kv_bytes_per_token"] == 2 * 3 * 2 * 64 * 4
    low = _program(tree, "bfloat16")
    widest = max(float(np.max(g["served"])) for g in ref.served_gaps(
        sz, 11, _served(low[0], sz)[0], "float32"))
    assert widest > 2e-3, widest
