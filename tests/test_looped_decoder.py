"""A stack run several times with the same parameters (``build_decoder_lm(
loops=, exit_gate=)``, ISSUE 49): the graph against the plain reference
(``perfbench/reference/ouro.py``, float32, ``Precision.HIGHEST``) in its
forward, through the paged cache by call site and under a gradient; a pass's
cache is its own; the exit gate's rule; a parameter with several owners
wherever it is reckoned (checkpoints, the quantizer, the memory and the
weight-sync accounting); and ``loops=1, exit_gate=None`` building the graphs
that were there.  The family, its cell and its readers are held in
``tests/perfbench/test_perfbench_ouro.py``.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu import GenerationEngine, MachineMesh
from flexflow_tpu.op import ServeStep
from flexflow_tpu.serving.generation.decoder import GraphDecoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, PAGE, VOCAB, LAYERS = 48, 4, 96, 2
SZ = {"layers": [{"attention": "full_attention", "heads": 4,
                  "mlp": "dense"}] * LAYERS,
      "d_model": 32, "head_dim": 8, "kv_heads": 4, "d_ff": 64,
      "vocab": VOCAB, "eps": 1e-6, "rope_theta": 1e4, "positions": SEQ,
      "weight_dtype": "float32"}


def _load(kind, name):
    path = os.path.join(REPO, "perfbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"looped_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "ouro")


def _sizes(passes, threshold=1.0):
    return dict(SZ, passes=passes, exit_threshold=threshold)


def _build(passes, threshold=1.0, dtype="float32", loss=False):
    from flexflow_tpu.models import build_decoder_lm
    cfg = ff.FFConfig(batch_size=2, compute_dtype=dtype, seed=0)
    cfg.serve_gen_slots, cfg.serve_gen_max_seq = 2, SEQ
    cfg.serve_prefill_chunk, cfg.serve_kv_page = 8, PAGE
    model = build_decoder_lm(
        cfg, SZ["layers"], d_model=32, head_dim=8, num_kv_heads=4, d_ff=64,
        vocab_size=VOCAB, seq_len=SEQ, rms_eps=1e-6, sandwich=True,
        rope={"full_attention": {"rope_theta": 1e4}}, loops=passes,
        exit_gate=threshold)[0]
    if loss:
        model.compile(ff.SGDOptimizer(lr=0.01),
                      "sparse_categorical_crossentropy", [],
                      mesh=MachineMesh({"n": 1}))
    else:
        model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    return model


def _install(model, ref, sz, seed=3):
    """The reference's weights of ``seed`` in the graph's parameters, each
    handed over once (``perfbench/families/ouro.py``'s ``install``)."""
    fam = _load("families", "ouro")
    fam.install(model, sz, ref.init_params(sz, seed))
    return model


def _program(ref, passes, threshold=1.0, dtype="float32"):
    sz = _sizes(passes, threshold)
    return _install(_build(passes, threshold, dtype), ref, sz), sz


@pytest.fixture(scope="module")
def looped(ref):
    return _program(ref, 4)


# ---------------------------------------------------------------------------
# the graph
# ---------------------------------------------------------------------------
def test_a_later_passs_ops_read_pass_ones_parameters():
    """The layer list laid three times: one running index a call site, the
    final norm after every pass, ONE gate and ONE head; the parameters are
    pass 1's, once each, and no parameter is left without a layer (FF006
    still fires for one that is)."""
    from flexflow_tpu.analysis.graph_passes import graph_diagnostics

    model = _build(3)
    names = [op.name for op in model.layers]
    assert names.count("lm_head") == names.count("exit_gate") == 1
    assert [n for n in names if n.startswith("attention_")] == [
        f"attention_{i}" for i in range(3 * LAYERS)]
    assert [n for n in names if n.startswith("ln_final")] == [
        "ln_final", "ln_final_1", "ln_final_2"]
    assert model.loop == (1, 11 * LAYERS + 1, 3)
    held = [p.name for p in model.parameters]
    assert len(held) == len(set(held)) == 11 * LAYERS + 5
    assert not [n for n in held if n.startswith(("attention_2", "ln_final_",
                                                 "ffn_up_3"))]
    ops = {op.name: op for op in model.layers}
    assert ops["attention_4"].w_q is ops["attention_0"].w_q
    assert ops["attention_4"].loop_source is ops["attention_0"]
    assert ops["attention_0"].loop_passes == 3
    assert ops["attention_4"].own_weights() == []
    assert len(ops["attention_0"].own_weights()) == 4
    assert ops["ln_final_2"].w_scale is ops["ln_final"].w_scale
    codes = lambda m: [d.code for d in graph_diagnostics(
        m.layers, m.input_tensors, parameters=m.parameters)]
    assert "FF006" not in codes(model)
    from flexflow_tpu.tensor import Parameter
    model.parameters.append(Parameter(shape=(3,), name="nobody/w"))
    assert "FF006" in codes(model)


@pytest.mark.parametrize("preset", ["tiny-xs2.serve", "tiny-pangu.serve",
                                    "tiny-sparse.serve"])
def test_one_loop_and_no_gate_build_the_graphs_that_were_there(preset):
    """The laguna, pangu and keye tiny presets through their families (which
    give neither keyword): the op list and the parameter names are the ones
    the parent built, written down here from the parent commit as a digest,
    no op has a loop source and the model no loop."""
    import hashlib
    import sys

    sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))
    import pb_tiny

    want = {"tiny-xs2.serve": "cf0108f2768628d8",
            "tiny-pangu.serve": "cfa215ce191656ce",
            "tiny-sparse.serve": "a77abd8259878579"}
    doc = next(p for p in pb_tiny.presets() if p["cell"]["name"] == preset)
    fam = _load("families", doc["config"]["family"])
    model = fam.build_serve(doc["config"], doc["traffic"])
    assert model.loop is None
    assert all(op.loop_source is None and op.loop_passes == 1
               for op in model.layers)
    text = json.dumps([[type(op).__name__, op.name,
                        [list(t.shape) for t in op.outputs]]
                       for op in model.layers]
                      + [[p.name, list(p.shape)] for p in model.parameters])
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want[preset]


# ---------------------------------------------------------------------------
# the graph against the reference
# ---------------------------------------------------------------------------
def _forward_gap(model, ref, sz, seed=3):
    tok = np.random.default_rng(1).integers(1, VOCAB, (2, SEQ)).astype(
        np.int32)
    got = np.log(np.asarray(model.predict([tok], batch_size=2), np.float64))
    want = np.asarray(jax.nn.log_softmax(ref.lm_logits(
        ref.init_params(sz, seed), tok, sz), axis=-1), np.float64)
    return float(np.abs(got - want).max())


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_forward_is_the_references_at_one_two_and_four_passes(ref,
                                                                  passes):
    """Log-probabilities over the whole vocabulary at 2 x 48 positions,
    float32 on both sides: within 1e-4 (the program's products are the
    backend's default float32, the reference's ``Precision.HIGHEST``)."""
    model, sz = _program(ref, passes)
    assert _forward_gap(model, ref, sz) <= 1e-4


def test_the_same_graph_in_bfloat16_fails_that_tolerance(ref):
    model, sz = _program(ref, 4, dtype="bfloat16")
    assert _forward_gap(model, ref, sz) > 3e-3


def _served_logits(model, prompt, steps, spoil=None):
    """Log-probabilities of a prompt prefilled in chunks of 8 and 5 then
    ``steps`` token steps of slot 0 through the paged cache (the best token
    fed back), as ``GraphDecoder``'s own programs compute them (the graph's
    final tensor is the softmax): ``(steps + 1, vocab)``.  ``spoil(caches, dec)`` edits the cache between
    prefill and decode."""
    dec = GraphDecoder(model, 2, SEQ, prefill_chunk=8)
    caches = dec.init_cache()
    table = jnp.arange(dec.pages_per_slot, dtype=jnp.int32)
    params = model._params

    def chunk(caches, toks, start, length):
        where = ServeStep("chunk", table, start=jnp.int32(start),
                          length=jnp.int32(length), slot=jnp.int32(0),
                          no_page=dec.num_pages)
        return jax.jit(lambda p, c, t: dec._walk(p, c, t, where))(
            params, caches, jnp.asarray(toks)[None])

    n = len(prompt)
    assert n == 13
    _, caches = chunk(caches, prompt[:8], 0, 8)
    tail = np.zeros((8,), np.int32)
    tail[:5] = prompt[8:]
    logits, caches = chunk(caches, tail, 8, 5)
    out = [np.asarray(logits[0, 4])]
    if spoil is not None:
        caches = spoil(caches, dec)
    tables = jnp.stack([table, table + dec.pages_per_slot])
    step = jax.jit(lambda p, c, t, pos, wp, wr: dec._walk_decode(
        p, c, t, pos, tables, wp, wr))
    for i in range(steps):
        pos = n + i
        tok = int(np.argmax(out[-1]))
        lg, caches = step(
            params, caches, jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32),
            jnp.asarray([pos // PAGE, dec.num_pages], jnp.int32),
            jnp.asarray([pos % PAGE, 0], jnp.int32))
        out.append(np.asarray(lg[0]))
    return np.log(np.stack(out).astype(np.float64))


def _reference_logits(ref, sz, prompt, served, seed=3):
    """The reference's full forward over prompt + the tokens fed back: the
    rows that predicted each served position."""
    full = np.concatenate([prompt, served]).astype(np.int32)
    logits = np.asarray(jax.nn.log_softmax(ref.lm_logits(
        ref.init_params(sz, seed), full[None], sz)[0], axis=-1), np.float64)
    return logits[len(prompt) - 1:]


def test_prefill_then_decode_through_the_cache_is_the_full_forward(ref,
                                                                   looped):
    """A 13-token prompt in chunks of 8 and 5 (pages of 4: across pages),
    then 6 token steps, four passes each with a region of its own in every
    layer's leaves, against the reference's full forward over all four
    passes: the LOGITS of every served position within 2e-4 (float32 on both
    sides).  The same run in bfloat16 reads over ten times that."""
    model, sz = looped
    prompt = np.random.default_rng(5).integers(1, VOCAB, 13).astype(np.int32)
    got = _served_logits(model, prompt, 6)
    fed = np.argmax(got[:-1], axis=-1)
    want = _reference_logits(ref, sz, prompt, fed)
    assert got.shape == want.shape == (7, VOCAB)
    assert float(np.abs(got - want).max()) <= 2e-4
    low, _ = _program(ref, 4, dtype="bfloat16")
    got_low = _served_logits(low, prompt, 6)
    want_low = _reference_logits(ref, sz, prompt,
                                 np.argmax(got_low[:-1], axis=-1))
    assert float(np.abs(got_low - want_low).max()) > 2e-3


def test_a_passs_cache_is_its_own(ref, looped):
    """Region ``t`` of a layer's leaves is pass ``t``'s: overwriting pass 2's
    pages of the prompt changes the decoded logits, and reading pass 1's
    rows in their place (pass 2's region filled with pass 1's) fails the
    comparison with the reference that the untouched cache passes."""
    model, sz = looped
    prompt = np.random.default_rng(6).integers(1, VOCAB, 13).astype(np.int32)
    sound = _served_logits(model, prompt, 2)

    def zero_pass_two(caches, dec):
        n = dec.num_pages
        return {name: {leaf: arr.at[n:2 * n].set(0) for leaf, arr
                       in sub.items()} if name == "attention_0" else sub
                for name, sub in caches.items()}

    def pass_one_for_two(caches, dec):
        n = dec.num_pages
        return {name: {leaf: arr.at[n:2 * n].set(arr[:n]) for leaf, arr
                       in sub.items()} if name.startswith("attention_")
                else sub for name, sub in caches.items()}

    dec = GraphDecoder(model, 2, SEQ, prefill_chunk=8)
    assert dec.layout["attention_0"]["shapes"]["k"] == (
        4 * dec.num_pages, PAGE, 32)
    assert "attention_2" not in dec.layout
    spoiled = _served_logits(model, prompt, 2, spoil=zero_pass_two)
    assert (spoiled[0] == sound[0]).all()       # the prefill came before
    assert float(np.abs(spoiled[1:] - sound[1:]).max()) > 1e-3
    fed = np.argmax(sound[:-1], axis=-1)
    want = _reference_logits(ref, sz, prompt, fed)
    assert float(np.abs(sound - want).max()) <= 2e-4
    shared = _served_logits(model, prompt, 2, spoil=pass_one_for_two)
    assert float(np.abs(shared[1:] - want[1:]).max()) > 1e-3


@pytest.mark.parametrize("threshold", [1.0, 0.6])
def test_the_exit_distribution_and_the_chosen_pass(ref, threshold):
    """The gate op on the graph's own states against the reference's rule:
    the distribution over the four passes and the pass each position leaves
    by, at the published threshold (every token the last pass) and at 0.6
    (positions leave by different passes; the gate's weight, N(0, 0.02)
    like the rest, is made 30 times larger on both sides so that the passes'
    exit probabilities differ); the op's output is the chosen pass's
    state."""
    from flexflow_tpu.op import OpContext

    model, sz = _program(ref, 4, threshold)
    tok = np.random.default_rng(7).integers(1, VOCAB, (1, SEQ)).astype(
        np.int32)
    params = ref.init_params(sz, 3)
    (states,) = ref.passes(params, list(tok), sz)
    want_p, want_exit = ref.exit_distribution(
        states, 30 * params.leaf("w_gate"), params.leaf("b_gate"), threshold)
    gate = next(op for op in model.layers if op.name == "exit_gate")
    mine = dict(model._params)
    mine["exit_gate/kernel"] = 30 * mine["exit_gate/kernel"]
    got_p, got_exit = gate.exit_distribution(
        mine, [h[None] for h in states])
    np.testing.assert_allclose(np.asarray(got_p[0]), np.asarray(want_p),
                               atol=1e-6)
    assert (np.asarray(got_exit[0]) == np.asarray(want_exit)).all()
    exits = set(np.asarray(want_exit).tolist())
    assert (exits == {3}) if threshold == 1.0 else (len(exits) > 1)
    ctx = OpContext(training=False, compute_dtype="float32")
    (out,) = gate.forward(mine, [h[None] for h in states], ctx)
    took = np.stack([np.asarray(h) for h in states])[
        np.asarray(want_exit), np.arange(SEQ)]
    np.testing.assert_array_equal(np.asarray(out[0]), took)


def test_the_engine_serves_the_graphs_tokens_reused_and_migrated(ref):
    """Through ``GenerationEngine`` at threshold 0.6 (tokens leave by
    different passes): the tokens are the graph's own forward's, also for a
    prompt that REUSES another's first pages (every pass's rows with them)
    and for streams that prefill on one engine and decode on another (a page
    shipped is the page of every pass's region); nothing is refused, and
    ``stats()`` carries the gate's counters and a token's bytes."""
    from test_generation import reference_decode
    from tests.serving_fixtures import build_disagg

    model, sz = _program(ref, 4, 0.6)
    # the gate's weight 30 times larger, so that the passes' exit
    # probabilities differ (N(0, 0.02) leaves every one at a half)
    model._params["exit_gate/kernel"] = 30 * model._params["exit_gate/kernel"]
    dec = GraphDecoder(model, 2, SEQ, prefill_chunk=8)
    assert dec.pageable and dec.refusal("migration") is None
    rng = np.random.default_rng(8)
    first = rng.integers(1, VOCAB, 22).astype(np.int32)
    second = np.concatenate([first[:17], rng.integers(1, VOCAB, 5)]).astype(
        np.int32)
    refs = [reference_decode(model, p, 8, SEQ) for p in (first, second)]
    with ff.fflogger.silenced("serve"):
        with GenerationEngine(model, slots=2) as eng:
            outs = [[int(t) for t in eng.submit(
                p, max_new_tokens=8).result(timeout=300)]
                for p in (first, second)]
            snap = eng.stats()
    assert outs == refs and snap["prefix_hit_tokens"] == 16
    # 2 layers x 4 passes x (K and V) x 32 values x 4 bytes
    assert snap["kv_bytes_per_token"] == 2 * 4 * 2 * 32 * 4
    assert snap["kv_pages"]["full"]["entries"] == LAYERS
    loop = snap["loop"]
    assert snap["loop_passes"] == 4 * loop["tokens"] > 0
    assert sum(loop["exits_by_pass"]) == loop["tokens"]
    assert sum(n > 0 for n in loop["exits_by_pass"]) > 1
    assert abs(sum(snap["exit_mass_by_pass"]) - 1.0) < 1e-3
    with ff.fflogger.silenced("serve"):
        router, fleets, _ = build_disagg(model, 2, SEQ, 8,
                                         prefix_cache="off", pf_pace_s=0.0)
        try:
            outs = [[int(t) for t in router.submit(
                "lm", p, max_new_tokens=8).result(timeout=300)]
                for p in (first, second)]
            stats = router.stats()
        finally:
            router.stop()
            for f in fleets:
                f.stop()
    assert outs == refs
    assert stats["migrations"] == 2 and stats["migrated_bytes"] > 0


def test_the_gradient_of_a_shared_parameter_sums_its_call_sites(ref):
    """One training step's gradient on the dense core: the graph's gradient
    of ``attention_0/wq`` and ``ffn_down_1/kernel`` (read at four call sites
    each) equals ``jax.grad`` of the reference's cross-entropy with respect
    to the ONE leaf all four passes read."""
    passes = 4
    sz = _sizes(passes)
    model = _install(_build(passes, loss=True), ref, sz)
    rng = np.random.default_rng(9)
    tok = rng.integers(1, VOCAB, (2, SEQ)).astype(np.int32)
    labels = rng.integers(0, VOCAB, (2, SEQ)).astype(np.int32)
    model.set_batch(tok, labels)
    model.backward()
    got = model._cached_grads
    params = ref.init_params(sz, 3)
    leaves = {(n, l): params.leaf(n, l) for l in range(LAYERS)
              for n in ref.LAYER}
    top = {n: params.leaf(n) for n in ref.TOP}

    def loss(wq0, w21):
        mine = dict(leaves)
        mine["wq", 0], mine["w2", 1] = wq0, w21
        total = 0.0
        for seq, lab in zip(tok, labels):
            x = jnp.take(top["tok_emb"], seq, axis=0)
            for _ in range(passes):
                for l in range(LAYERS):
                    x = ref.layer_step(
                        x, {n: mine[n, l] for n in ref.LAYER}, sz, l)
                x = ref.rms_norm(x, top["g_final"], sz["eps"])
            logp = jax.nn.log_softmax(
                jnp.matmul(x, top["head"], precision=ref.HI), axis=-1)
            total = total - jnp.sum(jnp.take_along_axis(
                logp, jnp.asarray(lab)[:, None], axis=-1))
        return total / tok.size

    want = jax.grad(loss, argnums=(0, 1))(leaves["wq", 0], leaves["w2", 1])
    for name, w in (("attention_0/wq", want[0]),
                    ("ffn_down_1/kernel", want[1])):
        g, w = np.asarray(got[name]), np.asarray(w).T
        assert float(np.abs(g - w).max()) <= 2e-3 * float(np.abs(w).max())
    assert "attention_2/wq" not in got


# ---------------------------------------------------------------------------
# a parameter with several owners, wherever it is reckoned
# ---------------------------------------------------------------------------
def test_a_shared_parameter_is_charged_and_reduced_once():
    """A two-call-site graph (two dense layers of one kernel) against the
    same graph unshared: the simulator's peak memory and its weight-sync
    time are the unshared graph's with ONE op's weights taken away."""
    from flexflow_tpu.search.cost_model import op_memory_components
    from flexflow_tpu.search.simulator import Simulator

    def build(shared):
        cfg = ff.FFConfig(batch_size=8)
        m = ff.FFModel(cfg)
        x = m.create_tensor((8, 64), name="x")
        a = m.dense(x, 64, use_bias=False, name="fc_a")
        m.dense(a, 64, use_bias=False, name="fc_b")
        if shared:
            m.share_weights(m.layers[1], m.layers[0])
        return m

    plain, shared = build(False), build(True)
    assert len(plain.parameters) == 2 and len(shared.parameters) == 1
    dims = (4, 1)
    state = [op_memory_components(op, dims)[0] for op in shared.layers]
    one = op_memory_components(plain.layers[0], dims)[0]
    assert state == [one, 0.0] and one > 0
    strategies = {op.name: ff.ParallelConfig.data_parallel(4, 2)
                  for op in plain.layers}
    sim_p, sim_s = Simulator(num_devices=4), Simulator(num_devices=4)
    sync_p = [sim_p._op_plan(op, strategies)[4] for op in plain.layers]
    sync_s = [sim_s._op_plan(op, strategies)[4] for op in shared.layers]
    assert sync_p[0] == sync_p[1] > 0
    assert sync_s == [sync_p[0], 0.0]
    peak_p = sim_p.peak_memory_bytes(plain.layers, strategies)
    peak_s = sim_s.peak_memory_bytes(shared.layers, strategies)
    assert peak_p - peak_s == pytest.approx(one)


def test_a_checkpoint_and_the_quantizer_hold_a_shared_parameter_once(
        ref, looped, tmp_path):
    """``save_checkpoint`` writes one entry a parameter of a looped graph
    and ``load_checkpoint`` into a second build serves the same logits;
    ``quantize_weights`` quantizes each shared kernel once (one report row a
    kernel, not one a call site) and every call site reads it."""
    from flexflow_tpu.serving.quantize import eligible_weights

    model, sz = looped
    path = str(tmp_path / "looped")
    model.save_checkpoint(path)
    with np.load(path + ".npz", allow_pickle=True) as z:
        stored = [k[len("param:"):] for k in z.files
                  if k.startswith("param:")]
    assert sorted(stored) == sorted(p.name for p in model.parameters)
    assert stored.count("attention_0/wq") == 1
    assert not [n for n in stored if "attention_2/" in n]
    other = _build(4)
    other.init_layers(seed=1)
    other.load_checkpoint(path)
    prompt = np.random.default_rng(5).integers(1, VOCAB, 13).astype(np.int32)
    a, b = _served_logits(model, prompt, 2), _served_logits(other, prompt, 2)
    assert (a == b).all()
    linear = [w.name for _, w in eligible_weights(other.layers)]
    assert len(linear) == len(set(linear)) == 3 * LAYERS + 1
    report = other.quantize_weights("int8")
    assert len(report["weights"]) == 3 * LAYERS + 1 and report["bound_ok"]
    q = _served_logits(other, prompt, 2)
    assert 0 < float(np.abs(q - a).max()) < 0.1
