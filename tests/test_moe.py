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
