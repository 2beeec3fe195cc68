"""MoE expert-parallelism tests (VERDICT round-2 ask #5).

Parity contracts: (a) a 1-expert MoE with ample capacity IS the plain FFN;
(b) the same MoE model produces identical results on a single device and on
a dp2 x ep4 mesh (expert weights sharded over 'e', token dispatch via
GSPMD all_to_all)."""

import numpy as np
import pytest

import flexflow_tpu as ff


def _data(rng, batch, s, d, classes=8):
    x = rng.standard_normal((batch, s, d)).astype(np.float32)
    y = rng.integers(0, classes, (batch, 1)).astype(np.int32)
    return x, y


def _build(mesh_shape, batch=16, s=8, d=32, E=4, k=2, cf=1.25, aux=1e-2,
           seed=0):
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    model = ff.FFModel(cfg, mesh=ff.MachineMesh(mesh_shape))
    x = model.create_tensor((batch, s, d), name="x")
    t = model.moe(x, E, d_ff=64, k=k, capacity_factor=cf,
                  aux_loss_weight=aux, name="moe0")
    t = model.flat(t)
    t = model.dense(t, 8, name="head")
    model.compile(ff.SGDOptimizer(lr=0.05),
                  "sparse_categorical_crossentropy", ["accuracy"],
                  final_tensor=t)
    model.init_layers(seed=seed)
    return model


def test_single_expert_equals_dense_ffn():
    rng = np.random.default_rng(0)
    batch, s, d = 4, 6, 16
    cfg = ff.FFConfig(batch_size=batch, compute_dtype="float32")
    model = ff.FFModel(cfg, mesh=ff.MachineMesh({"n": 1}))
    x = model.create_tensor((batch, s, d), name="x")
    model.moe(x, num_experts=1, d_ff=32, k=1, capacity_factor=1.0,
              activation="relu", aux_loss_weight=0.0, name="moe0")
    model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", [],
                  final_tensor=model.layers[-1].outputs[0])
    model.init_layers(seed=3)
    xd = rng.standard_normal((batch, s, d)).astype(np.float32)
    out = model.predict(xd, batch_size=batch)
    # expert weights are stored (in, out), as the grouped product reads them
    w1 = model.get_weights("moe0/w_up")[0]      # (d, d_ff)
    b1 = model.get_weights("moe0/w_up_bias")[0]
    w2 = model.get_weights("moe0/w_down")[0]    # (d_ff, d)
    b2 = model.get_weights("moe0/w_down_bias")[0]
    ref = np.maximum(xd @ w1 + b1, 0.0) @ w2 + b2
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_moe_mesh_parity_dp_ep():
    """Same seed, same data: single-device == dp2/ep4 sharded execution."""
    rng = np.random.default_rng(1)
    xd, yd = _data(rng, 16, 8, 32)
    m1 = _build({"n": 1})
    m2 = _build({"n": 2, "expert": 4})
    assert m2.mesh.axis_size("e") == 4
    p1 = m1.predict(xd)
    p2 = m2.predict(xd)
    np.testing.assert_allclose(p1, p2, rtol=2e-4, atol=2e-4)
    l1 = [float(m1.train_batch(xd, yd)) for _ in range(3)]
    l2 = [float(m2.train_batch(xd, yd)) for _ in range(3)]
    np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)
    assert l1[-1] < l1[0]  # actually learning


def test_capacity_drops_tokens():
    """A tiny capacity factor forces overflow: outputs for dropped tokens
    are zero-combined, so shrinking capacity must change the output."""
    rng = np.random.default_rng(2)
    xd = rng.standard_normal((8, 4, 16)).astype(np.float32)
    outs = []
    for cf in (4.0, 0.25):
        cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
        model = ff.FFModel(cfg, mesh=ff.MachineMesh({"n": 1}))
        x = model.create_tensor((8, 4, 16), name="x")
        model.moe(x, num_experts=4, d_ff=32, k=1, capacity_factor=cf,
                  name="moe0")
        model.compile(ff.SGDOptimizer(lr=0.1), "mean_squared_error", [],
                      final_tensor=model.layers[-1].outputs[0])
        model.init_layers(seed=5)
        outs.append(model.predict(xd, batch_size=8))
    assert np.abs(outs[0] - outs[1]).max() > 1e-4


def test_aux_loss_feeds_objective():
    rng = np.random.default_rng(3)
    xd, yd = _data(rng, 16, 8, 32)
    m_aux = _build({"n": 1}, aux=0.5, seed=7)
    m_no = _build({"n": 1}, aux=0.0, seed=7)
    la = float(m_aux.train_batch(xd, yd))
    ln = float(m_no.train_batch(xd, yd))
    # Switch aux loss is ~1 for a fresh router; weight 0.5 must show up
    assert la > ln + 0.1


def test_one_dispatch_matches_a_loop_over_tokens_and_experts():
    """The op (gated experts, a shared one, dropless, routed scale) against
    a loop over tokens and experts written out in numpy, with the router
    rigged so that ONE expert is given every token (its group is the whole
    batch) and one is given none (an empty group between two others).
    Tolerance 2e-5: float32 on both sides; the op's grouped products sum
    in another order than the loop's dot products."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.op import OpContext
    from flexflow_tpu.ops.moe import MoE
    from flexflow_tpu.tensor import Tensor

    rng = np.random.default_rng(4)
    n, s, d, E, k, f, fs = 3, 5, 16, 6, 2, 8, 12
    x = Tensor(shape=(n, s, d), dtype="float32", name="x")
    op = MoE("moe", x, E, f, k=k, capacity_factor=None, aux_loss_weight=0.0,
             gated=True, shared_d_ff=fs, routed_scale=2.5)
    params = {w.name: rng.standard_normal(w.shape).astype(np.float32) * 0.3
              for w in op.weights}
    gate = params["moe/gate"]
    xd = rng.standard_normal((n, s, d)).astype(np.float32)
    # expert 2 wins everywhere, expert 4 nowhere: a positive direction
    # every token shares, added to the one router row and taken from the
    # other
    xd[..., 0] = 3.0 + np.abs(xd[..., 0])
    gate[2, 0], gate[4, 0] = 4.0, -4.0
    ctx = OpContext(training=False, compute_dtype="float32", mesh=None)
    got = np.asarray(op.forward({k_: jnp.asarray(v) for k_, v in
                                 params.items()}, [jnp.asarray(xd)], ctx)[0])

    def silu(v):
        return v / (1.0 + np.exp(-v))

    def ffn(v, up, down, width):
        h = v @ up
        return (silu(h[:width]) * h[width:]) @ down

    want = np.zeros_like(xd)
    chosen = np.zeros(E, int)
    for i in range(n):
        for j in range(s):
            v = xd[i, j]
            logits = gate @ v
            p = np.exp(logits - logits.max())
            p /= p.sum()
            top = np.argsort(-p, kind="stable")[:k]
            out = ffn(v, params["moe/shared_up"], params["moe/shared_down"],
                      fs)
            for e in top:
                chosen[e] += 1
                out = out + 2.5 * p[e] / p[top].sum() * ffn(
                    v, params["moe/w_up"][e], params["moe/w_down"][e], f)
            want[i, j] = out
    assert chosen[2] == n * s and chosen[4] == 0, chosen
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # what the op counts while it serves: the same histogram
    from flexflow_tpu.op import ServeStep
    state = {"load": jnp.zeros((E,), jnp.int32),
             "token_steps": jnp.zeros((), jnp.int32),
             "untouched": jnp.zeros((), jnp.int32)}
    where = ServeStep("token", None, pos=jnp.zeros((n * s,), jnp.int32),
                      write_pages=jnp.zeros((n * s,), jnp.int32), no_page=9)
    _, new = op.serve_step({k_: jnp.asarray(v) for k_, v in params.items()},
                           [jnp.asarray(xd.reshape(n * s, 1, d))], state,
                           where, ctx)
    assert np.asarray(new["load"]).tolist() == chosen.tolist()
    assert int(new["token_steps"]) == 1
    assert int(new["untouched"]) == int((chosen == 0).sum())


def test_an_moe_with_a_capacity_refuses_to_serve():
    from flexflow_tpu.ops.moe import MoE
    from flexflow_tpu.tensor import Tensor

    x = Tensor(shape=(2, 4, 8), dtype="float32", name="x")
    MoE("free", x, 4, 8, capacity_factor=None).serve_check(16)
    with pytest.raises(ValueError, match="capacity"):
        MoE("capped", x, 4, 8, capacity_factor=1.25).serve_check(16)


def test_the_simulator_prices_the_ops_of_a_sparse_windowed_decoder():
    """ROADMAP D5: an op the search cannot cost is not supported.  The
    decoder's graph (grouped windowed attention, a dropless gated MoE
    beside a shared expert) through ``Simulator.simulate`` and the search;
    the MoE's count is the router over all experts plus ``k`` routed
    experts and the shared one a token, whatever the routing; a window
    shortens the attention's key range."""
    from flexflow_tpu.models import build_decoder_lm
    from flexflow_tpu.search.mcmc import search
    from flexflow_tpu.search.simulator import Simulator

    layers = [{"attention": "full_attention", "heads": 6, "mlp": "dense"},
              {"attention": "sliding_attention", "heads": 8, "mlp": "sparse"}]
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32")
    model = build_decoder_lm(
        cfg, layers, d_model=32, head_dim=16, num_kv_heads=2, d_ff=64,
        vocab_size=64, seq_len=32, window=8,
        rope={"full_attention": {"rope_theta": 1e4},
              "sliding_attention": {"rope_theta": 1e4}}, gate=True,
        moe={"num_experts": 8, "k": 2, "d_ff": 16, "shared_d_ff": 16,
             "routed_scale": 2.5})[0]
    ops = {op.name: op for op in model.layers}
    n, s, d = 4, 32, 32
    moe = ops["moe_1"]
    assert moe.flops() == 2 * n * s * (d * 8 + 2 * 3 * d * 16 + 3 * d * 16)
    assert moe.weight_bytes() == 4 * sum(w.volume for w in moe.weights)
    assert moe.sub_problem((2, 1, 1))[0] == [(2, s, d)]
    full, sliding = ops["attention_0"], ops["attention_1"]
    proj = lambda op: 2 * n * s * sum(    # noqa: E731
        w.volume for w in op.weights)
    assert full.flops() == proj(full) + 4 * n * s * s * 6 * 16
    assert sliding.flops() == proj(sliding) + 4 * n * s * 8 * 8 * 16
    # at shapes the flash kernel takes, a grouped or windowed op still runs
    # the dense core (``_attend``): the simulator charges its score matrix,
    # whole (the window masks it), and nothing for a plain op's flash kernel
    big = build_decoder_lm(
        ff.FFConfig(batch_size=2, compute_dtype="bfloat16"), layers,
        d_model=128, head_dim=128, num_kv_heads=2, d_ff=64, vocab_size=64,
        seq_len=512, window=128,
        rope={"full_attention": {"rope_theta": 1e4},
              "sliding_attention": {"rope_theta": 1e4}}, gate=True,
        moe={"num_experts": 8, "k": 2, "d_ff": 16, "shared_d_ff": 16,
             "routed_scale": 2.5})[0]
    for op in big.layers:
        if op.name in ("attention_0", "attention_1"):
            assert op.internal_io_bytes() == 12 * 2 * op.num_heads * 512 * 512
    plain = ff.FFModel(ff.FFConfig(batch_size=2, compute_dtype="bfloat16"))
    x = plain.create_tensor((2, 512, 256), name="x")
    plain.multihead_attention(x, x, x, 256, 2, causal=True)
    assert plain.layers[-1].head_dim == 128
    assert plain.layers[-1].internal_io_bytes() == 0
    sim = Simulator(num_devices=4, use_native=False)
    t = sim.simulate(model.layers, {}, mesh_shape={"n": 4})
    assert np.isfinite(t) and t > 0
    best = search(model.layers, 4, budget=20, seed=0)
    assert best


# ---------------------------------------------------------------------------
# a sigmoid router, and an op told which experts it holds (ISSUE 41)
# ---------------------------------------------------------------------------
_SHARE_SZ = {"layers": [{"attention": "latent_attention", "heads": 4,
                         "mlp": "sparse"}],
             "d_model": 64, "q_rank": 24, "kv_rank": 32, "nope": 16,
             "rope": 8, "v": 16, "rope_theta": 25.6e6, "d_ff": 128,
             "vocab": 64, "eps": 1e-5, "experts": 16, "router_experts": 16,
             "first_expert": 0, "k": 4, "expert_ff": 16, "shared_ff": 16,
             "routed_scale": 2.5, "positions": 32, "weight_dtype": "float32"}


def _pangu_reference():
    """``perfbench/reference/pangu_moe.py``, loaded by its path."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "reference", "pangu_moe.py")
    spec = importlib.util.spec_from_file_location("pangu_moe_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _share(ref, first, held, seed=5):
    """``(op, params, the share's sizes, the reference's leaves)`` of the
    sparse layer's op holding experts ``first .. first + held`` of 16, with
    the reference's weights for exactly those experts."""
    import jax.numpy as jnp

    from flexflow_tpu.ops.moe import MoE
    from flexflow_tpu.tensor import Tensor

    sz = dict(_SHARE_SZ, experts=held, first_expert=first)
    p = ref.init_params(sz, seed).layer(0)
    x = Tensor(shape=(2, 24, sz["d_model"]), dtype="float32", name="x")
    op = MoE("moe", x, sz["router_experts"], sz["expert_ff"], k=sz["k"],
             capacity_factor=None, aux_loss_weight=0.0, gated=True,
             shared_d_ff=sz["shared_ff"], routed_scale=sz["routed_scale"],
             scoring="sigmoid", held=(first, held))
    params = {"moe/gate": p["wr"].T,
              "moe/w_up": jnp.concatenate([p["e1"], p["e3"]], axis=-1),
              "moe/w_down": p["e2"],
              "moe/shared_up": jnp.concatenate([p["s1"], p["s3"]], axis=-1),
              "moe/shared_down": p["s2"]}
    assert {w.name: tuple(w.shape) for w in op.weights} == {
        k: tuple(v.shape) for k, v in params.items()}
    return op, params, sz, p


def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    """Four ops that each hold 4 of 16 experts (router whole, 4 choices a
    token, sigmoid scores), and the uncut op: every share's output is the
    reference's for that share, the uncut op's the uncut reference's, and
    the ROUTED parts of the four shares with the shared expert counted
    once add up to the uncut reference's layer output.  Tolerance 2e-5:
    float32 on both sides, the op's grouped products against the
    reference's ``Precision.HIGHEST`` loop over every held expert."""
    import jax.numpy as jnp

    from flexflow_tpu.op import OpContext

    ref = _pangu_reference()
    ctx = OpContext(training=False, compute_dtype="float32", mesh=None)
    b = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, 24, _SHARE_SZ["d_model"])), jnp.float32)
    flat = b.reshape(-1, b.shape[-1])

    def run(first, held):
        op, params, sz, p = _share(ref, first, held)
        got = np.asarray(op.forward(params, [b], ctx)[0]).reshape(flat.shape)
        want = np.asarray(ref.moe(flat, p, sz))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
        shared = np.asarray(ref.gated_ffn(flat, p["s1"], p["s3"], p["s2"]))
        return got, shared, op

    whole, shared, op = run(0, 16)
    assert op.flops() > run(0, 4)[2].flops()    # of the experts HELD
    parts = [run(first, 4) for first in (0, 4, 8, 12)]
    for got, sh, _ in parts:
        np.testing.assert_array_equal(sh, shared)   # every chip's alike
        assert np.abs(got - sh).max() > 1e-3        # and its experts add
    total = shared + sum(got - sh for got, sh, _ in parts)
    np.testing.assert_allclose(total, whole, rtol=0, atol=4e-5)


def test_the_sigmoid_router_picks_and_weights_as_the_reference_ties_included():
    """``scoring="sigmoid"``: the 4 largest sigmoid scores of 16, in float32,
    renormalised then times 2.5, against the reference's ``route``; two
    router rows made EQUAL (a tie at every token, inside the top four for
    most: a direction every token shares) are chosen lower number first on
    both sides.  Softmax scoring keeps choosing what it chose."""
    import jax.numpy as jnp

    ref = _pangu_reference()
    op, params, sz, p = _share(ref, 0, 16)
    rng = np.random.default_rng(7)
    xt = rng.standard_normal((48, sz["d_model"])).astype(np.float32)
    xt[:, 0] = 2.0 + np.abs(xt[:, 0])
    wr = np.array(p["wr"])
    wr[0, 5] = 1.0                 # expert 5 high everywhere ...
    wr[:, 9] = wr[:, 5]            # ... and expert 9 its twin
    params = dict(params, **{"moe/gate": jnp.asarray(wr.T)})
    idx, gates, scores = op._route(params, jnp.asarray(xt))
    want_idx, want_w = ref.route(jnp.asarray(xt), jnp.asarray(wr), sz)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_w),
                               rtol=0, atol=1e-6)
    idx = np.asarray(idx)
    both = (idx == 5).any(axis=1) & (idx == 9).any(axis=1)
    assert both.mean() > 0.5
    for row in idx[both]:          # of equal scores, the lower number first
        assert list(row).index(5) + 1 == list(row).index(9)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5, atol=1e-5)
    assert float(np.asarray(scores).max()) < 1.0    # sigmoid, not softmax
    assert np.asarray(scores).sum(axis=1).max() > 1.5
    op.scoring = "softmax"
    _, soft, probs = op._route(params, jnp.asarray(xt))
    np.testing.assert_allclose(np.asarray(probs).sum(axis=1), 1.0, atol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        _share_bad = ff.FFModel(ff.FFConfig(batch_size=2))
        _share_bad.moe(_share_bad.create_tensor((2, 4, 8), name="x"), 4, 8,
                       scoring="tanh")


def test_the_counters_of_a_share_speak_of_the_experts_held():
    """``serve_state`` and ``serve_step`` of an op that holds experts 4-7 of
    16: ``load`` has 4 entries and counts only the pairs that fell on them
    (a choice of an expert held elsewhere, below OR above, is dropped, not
    wrapped), ``untouched`` is of those 4."""
    import jax.numpy as jnp

    from flexflow_tpu.op import OpContext, ServeStep

    ref = _pangu_reference()
    op, params, sz, p = _share(ref, 4, 4)
    ent = op.serve_state(2, 8, 16, None)
    assert ent["shapes"]["load"] == (4,)
    ctx = OpContext(training=False, compute_dtype="float32", mesh=None)
    b = jnp.asarray(np.random.default_rng(8).standard_normal(
        (2, 1, sz["d_model"])), jnp.float32)
    state = {"load": jnp.zeros((4,), jnp.int32),
             "token_steps": jnp.zeros((), jnp.int32),
             "untouched": jnp.zeros((), jnp.int32)}
    where = ServeStep("token", None, pos=jnp.zeros((2,), jnp.int32),
                      write_pages=jnp.asarray([0, 8], jnp.int32),
                      write_rows=jnp.zeros((2,), jnp.int32), no_page=8)
    _, new = op.serve_step(params, [b], state, where, ctx)
    idx, _ = ref.route(b[:1, 0], p["wr"], sz)      # slot 0 is the live one
    want = np.bincount([i - 4 for i in np.asarray(idx)[0] if 4 <= i < 8],
                       minlength=4)
    np.testing.assert_array_equal(np.asarray(new["load"]), want)
    assert int(new["token_steps"]) == 1
    assert int(new["untouched"]) == int((want == 0).sum())


# ---------------------------------------------------------------------------
# an op that holds FEWER experts than its router scores dispatches its own
# pairs only, a block of rows at a time (ISSUE 45)
# ---------------------------------------------------------------------------
_OWN = {"tokens": 50, "d": 24, "experts": 16, "k": 4, "first": 4, "held": 4}
_OWN_BLOCK = 96     # MoE.block_rows(50, 4, 4, 16): 16-row tiles, 6.25 -> 6


def _rigged_share(own, gated, capacity_factor=None):
    """``(op, params, x (1, 50, d))`` of an op that holds experts 4-7 of 16
    with the ROUTER's weights set (row ``e`` reads input value ``e`` alone)
    and the inputs carrying each token's scores, so that exactly ``own`` of
    the 50 x 4 pairs fall on the held experts, spread over the tokens."""
    import jax.numpy as jnp

    from flexflow_tpu.ops.moe import MoE
    from flexflow_tpu.tensor import Tensor

    T, d, E, k = (_OWN[n] for n in ("tokens", "d", "experts", "k"))
    first, held = _OWN["first"], _OWN["held"]
    rng = np.random.default_rng(own)
    op = MoE("moe", Tensor(shape=(1, T, d), dtype="float32", name="x"), E, 12,
             k=k, capacity_factor=capacity_factor, aux_loss_weight=0.0,
             gated=gated, held=(first, held))
    params = {w.name: rng.standard_normal(w.shape).astype(np.float32) * 0.05
              for w in op.weights}            # outputs of order 1
    gate = np.zeros((E, d), np.float32)
    gate[np.arange(E), np.arange(E)] = 1.0
    params["moe/gate"] = gate
    x = rng.standard_normal((T, d)).astype(np.float32)
    x[:, :E] = -4.0
    each = np.full(T, own // T)
    each[:own % T] += 1
    elsewhere = np.setdiff1d(np.arange(E), np.arange(first, first + held))
    for t, n in enumerate(rng.permutation(each)):
        chosen = np.concatenate([
            first + rng.choice(held, n, replace=False),
            rng.choice(elsewhere, k - n, replace=False)])
        x[t, chosen] = rng.permutation(1.0 + np.arange(k))   # distinct
    return (op, {n: jnp.asarray(v) for n, v in params.items()},
            jnp.asarray(x[None]))


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "biased"])
@pytest.mark.parametrize("own", [
    0, 1, _OWN_BLOCK - 1, _OWN_BLOCK, _OWN_BLOCK + 1, 2 * _OWN_BLOCK + 5, 200])
def test_an_op_that_holds_a_share_dispatches_its_own_pairs_in_blocks(
        own, gated):
    """The walk over an op's OWN pairs in blocks of ``block_rows`` against
    the one pass over all pairs (``ctx.training=True``, forward only: the
    form a gradient is taken through), on an op holding 4 of 16 experts
    with the routing rigged to give it no pair, one, a block less one, a
    block, a block and one, three blocks with a ragged tail (2 x 96 + 5)
    and ALL 200: the same output in float32 (1e-6 on outputs of order 1:
    the same products, a token's up to 4 of them summed by a one-hot
    product where the one pass scatter-added them and exact zeros), and
    the counters of the serving step exact."""
    import jax.numpy as jnp

    from flexflow_tpu.op import OpContext, ServeStep
    from flexflow_tpu.ops.moe import MoE

    T, k = _OWN["tokens"], _OWN["k"]
    assert MoE.block_rows(T, k, _OWN["held"], _OWN["experts"]) == _OWN_BLOCK
    op, params, x = _rigged_share(own, gated)
    serving = OpContext(training=False, compute_dtype="float32", mesh=None)
    want = np.asarray(op.forward(params, [x], OpContext(
        training=True, compute_dtype="float32", mesh=None))[0])
    assert op.dispatch == {("forward", T): "whole"}
    got = np.asarray(op.forward(params, [x], serving)[0])
    assert op.dispatch == {("forward", T): {"rows": _OWN_BLOCK, "of": T * k}}
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    assert (np.abs(want).max() > 0.01) == (own > 0)
    assert np.abs(want).max() < 4.0
    ent = op.serve_state(2, 8, 16, None)
    assert set(ent["shapes"]) == {"load", "token_steps", "untouched",
                                  "dispatches", "routed", "own_pairs",
                                  "blocks", "past_one"}
    state = {n: jnp.full(shape, 7, jnp.int32)
             for n, shape in ent["shapes"].items()}
    where = ServeStep("chunk", None, start=jnp.int32(0),
                      length=jnp.int32(T - 3), slot=jnp.int32(0), no_page=8)
    out, new = op.serve_step(params, [x], state, where, serving)
    np.testing.assert_array_equal(np.asarray(out[0]), got)
    blocks = -(-own // _OWN_BLOCK)
    assert {n: int(new[n]) - 7 for n in op._DISPATCHED} == {
        "dispatches": 1, "routed": T, "own_pairs": own, "blocks": blocks,
        "past_one": int(blocks > 1)}
    # the histogram is of LIVE tokens (the chunk's last 3 rows are pad)
    assert 0 <= int(np.asarray(new["load"] - 7).sum()) <= own


def test_a_capacity_zeroes_by_rank_before_the_blocks_are_cut():
    """With a capacity (4 pairs an expert here) the weights past an
    expert's rank are zeroed on the SORTED pairs, before the op's own are
    cut into blocks: the walk agrees with the one pass, and with a
    capacity no pair could pass, it differs."""
    from flexflow_tpu.op import OpContext

    op, params, x = _rigged_share(2 * _OWN_BLOCK + 5, True,
                                  capacity_factor=0.32)
    assert op.capacity == 4
    out = {t: np.asarray(op.forward(params, [x], OpContext(
        training=t, compute_dtype="float32", mesh=None))[0])
        for t in (True, False)}
    np.testing.assert_allclose(out[False], out[True], rtol=0, atol=1e-6)
    loose = _rigged_share(2 * _OWN_BLOCK + 5, True, capacity_factor=8.0)[0]
    got = np.asarray(loose.forward(params, [x], OpContext(
        training=False, compute_dtype="float32", mesh=None))[0])
    assert np.abs(got - out[False]).max() > 1e-3


@pytest.mark.parametrize("E, cf", [(4, 1.25), (16, None)],
                         ids=["capacity", "dropless"])
def test_the_shards_of_an_e_axis_dispatch_their_own_pairs(E, cf):
    """Under ``{"n": 2, "expert": 4}`` every shard holds ``E / 4`` experts:
    ``predict`` walks each shard's own pairs in blocks (with a capacity its
    zero weights are set by rank first, tokens whole on every shard;
    dropless the tokens are sharded too) and agrees with one device, whose
    op holds every expert and takes the one pass; ``train_batch`` takes
    the one pass on the mesh too (a gradient)."""
    rng = np.random.default_rng(11)
    xd, yd = _data(rng, 16, 8, 32)
    m1 = _build({"n": 1}, E=E, cf=cf)
    m2 = _build({"n": 2, "expert": 4}, E=E, cf=cf)
    np.testing.assert_allclose(m1.predict(xd), m2.predict(xd), rtol=2e-4,
                               atol=2e-4)
    (op1,), (op2,) = ([op for op in m.layers if op.name == "moe0"]
                      for m in (m1, m2))
    T = 16 * 8 // (1 if cf else 2)      # dropless: a token shard's
    assert op1.dispatch == {("forward", 128): "whole"}
    assert op2.dispatch == {("forward", 128): {
        "rows": op2.block_rows(T, 2, E // 4, E), "of": 2 * T}}
    np.testing.assert_allclose(float(m1.train_batch(xd, yd)),
                               float(m2.train_batch(xd, yd)), rtol=2e-4)
    assert op2.dispatch == {("forward", 128): "whole"}
