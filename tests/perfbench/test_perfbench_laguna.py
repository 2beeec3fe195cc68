"""The Laguna family (ISSUE 36) and its cell, ``laguna-xs2.serve.closed-128-code``.

The cell is listed in BENCHMARK.json as new entries at the end of their
lists; its tiny preset lies under ``data/tiny/`` and its record of two sets
of six under ``data/serve_spreads/``, so ``test_perfbench_contract.py`` holds
it to the serve bounds and runs its preset traced and untraced beside the
others.  Here: the listing against ISSUE 36's lists, the tiny cell against
the family's reference, and the float8 control coming out not correct.

Then the program against the plain reference on the CPU in float32: the
graph's ``forward`` (logits), and prefill in chunks then decoding through the
engine's cache (the reference's best token at every served position)."""

import os

import numpy as np
import pytest

import pb_control
import pb_tiny

CELL = "laguna-xs2.serve.closed-128-code"
PRESET = "tiny-xs2.serve"
SETTER = "gpt1.serve.closed-128"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pblaguna"))


def _cell(tree, name):
    from perfbench.harness import cells

    return cells.load(tree, name)


def test_the_cell_is_listed_last_and_on_the_lists_issue_36_names():
    """The LAST configuration and cell, on one chip, on every list that
    names the serve cell that was there but ``paged_decode_roofline``'s
    (its reader cannot price a window), with its three readers listed for
    it alone."""
    bench = pb_tiny._json(os.path.join(pb_tiny.REPO, "BENCHMARK.json"))
    assert bench["configs"][-1]["name"] == "laguna-xs2"
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config="laguna-xs2",
        traffic="closed-128-code", chips=1)
    metrics = bench["end_to_end"] + bench["per_layer"]
    on = {m["name"] for m in metrics if CELL in m.get("workloads", ())}
    new = {"window_decode_roofline", "moe_decode_roofline", "moe_share"}
    assert new < on and "paged_decode_roofline" not in on
    assert on - new == {m["name"] for m in metrics if SETTER in m.get(
        "workloads", ())} - {"paged_decode_roofline"}
    for m in metrics:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    for m in bench["per_layer"][-3:]:
        assert m["name"] in new and m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(
            pb_tiny.REPO, "perfbench", "layer_metrics", m["name"] + ".py"))
    cell = _cell(pb_tiny.REPO, CELL)
    assert cell.config["family"] == "laguna"
    assert set(cell.doc["limits"]) == {"served_gap_mean",
                                       "served_gap_widest"}


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
    source is not the device trace, none whose source is, and the
    ``decode_step`` spans carry what the sparse layers counted (what
    ``moe_decode_roofline`` reads beside the trace on the chip)."""
    from perfbench.harness import cells

    cell = cells.load(tree, PRESET)
    result = pb_tiny.run(tree, PRESET, seed=2**31 + 36, seconds=1.0, trace=1)
    assert result["correct"] is True, capsys.readouterr().out
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert want and set(result["metrics"]) == want
    reader = cell.module("layer_metrics", "moe_decode_roofline")
    spans = [{"name": "decode_step", "args": {"moe_expert_steps": e,
                                              "moe_untouched": u}}
             for e, u in ((64, 10), (128, 30), (192, 42))]
    assert reader.touched_share(spans) == pytest.approx(1 - 32 / 128)
    assert reader.touched_share(spans[:1]) is None
    assert reader.touched_share([{"name": "decode_step", "args": {}}]) is None


# ---------------------------------------------------------------------------
# the Laguna family (ISSUE 36): the program against its reference in float32
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def laguna(tree):
    """The tiny Laguna cell's graph built by its family through the normal
    path, COMPUTING in float32 (the cell computes in bfloat16; the weights
    are the bfloat16-rounded ones on both sides either way), with the
    reference's weights of seed 11 installed."""
    cell = _cell(tree, "tiny-xs2.serve")
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    config = dict(cell.config, run=dict(cell.config["run"],
                                        compute_dtype="float32"))
    sz = fam.sizes(config)
    model = fam.build_serve(config, cell.traffic)
    fam.install(model, sz, ref.init_params(sz, 11))
    return model, ref, sz


def test_the_laguna_graphs_forward_agrees_with_its_reference(laguna):
    """Logits of the graph's ``forward`` (``predict``: full attention, no
    cache) against the reference's, as log-probabilities over the whole
    vocabulary at 2 x 64 positions.  Tolerance 2e-4: float32 on both sides
    with the same bfloat16-rounded weights; the program's products are the
    backend's default float32 and sum in another order than the reference's
    ``Precision.HIGHEST`` ones."""
    import jax
    import jax.numpy as jnp

    model, ref, sz = laguna
    tok = np.random.default_rng(1).integers(1, sz["vocab"], (2, 64)).astype(
        np.int32)
    got = np.log(np.asarray(model.predict([tok], batch_size=2), np.float64))
    want = np.asarray(jax.nn.log_softmax(ref.lm_logits(
        ref.init_params(sz, 11), tok, sz), axis=-1), np.float64)
    assert got.shape == want.shape == (2, 64, sz["vocab"])
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    # causal, and windowed where it says so: changing a token changes no
    # earlier position's logits
    other = tok.copy()
    other[:, 40:] = (other[:, 40:] + 5) % sz["vocab"]
    a = ref.lm_logits(ref.init_params(sz, 11), tok, sz)
    b = ref.lm_logits(ref.init_params(sz, 11), other, sz)
    assert float(jnp.max(jnp.abs(a[:, :40] - b[:, :40]))) == 0.0
    assert float(jnp.max(jnp.abs(a[:, 40:] - b[:, 40:]))) > 0.0


def test_laguna_served_tokens_are_the_references_best_at_every_position(
        laguna):
    """Prefill in chunks of 8 then decoding through the engine's cache,
    against the reference's full forward at every served position: prompts
    under the window (8), a chunk that straddles it (13), contexts that
    pass it several times (30 + 24).  In float32 the served token's
    reference logit lies within 2e-4 of the reference's best everywhere
    (it IS the best unless two logits tie within the arithmetic's noise)."""
    import flexflow_tpu as ff
    from flexflow_tpu import fflogger

    model, ref, sz = laguna
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, sz["vocab"], n).astype(np.int32)
               for n in (5, 13, 21, 30)]
    with fflogger.silenced("serve"):
        with ff.GenerationEngine(model, slots=2, max_new_tokens=24) as eng:
            streams = [eng.submit(p, max_new_tokens=24) for p in prompts]
            served = [[int(t) for t in s.result(timeout=300)]
                      for s in streams]
    gaps = ref.served_gaps(sz, 11, list(zip(prompts, served)), "float32")
    for g, s in zip(gaps, served):
        assert len(g["served"]) == len(s) == 24
        assert float(np.max(g["served"])) <= 2e-4, g["served"]
