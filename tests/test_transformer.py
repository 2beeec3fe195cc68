"""Transformer + attention tests: single-device training, ring-attention
numerics (dense vs ring, causal and not), and DP/SP/TP parity on the
8-device CPU mesh (BASELINE.json config 5; the reference has no attention
ops — SURVEY §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import ParallelConfig
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.ops import attention as attn_mod
from flexflow_tpu.ops import flash_kernel
from flexflow_tpu.ops.attention import _dense_attention, ring_attention
from flexflow_tpu.parallel.mesh import MachineMesh


def _data(b=8, s=16, vocab=100, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, (b, s)).astype(np.int32)
    y = rng.integers(0, classes, (b, 1)).astype(np.int32)
    return x, y


def _train(mesh_shape, strategies=None, steps=4, causal=False, seed=0):
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    if strategies:
        cfg.strategies = strategies
    model, tokens, logits = build_transformer(
        cfg, num_layers=2, d_model=64, num_heads=4, d_ff=128, seq_len=16,
        vocab_size=100, num_classes=4, causal=causal)
    model.compile(ff.SGDOptimizer(lr=0.05),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [],
                  final_tensor=logits, mesh=MachineMesh(mesh_shape))
    model.init_layers(seed=seed)
    x, y = _data()
    return [float(model.train_batch(x, y)) for _ in range(steps)]


def test_transformer_trains_single_device():
    losses = _train({"n": 1}, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_transformer_dp_sp_parity():
    """DP x ring-attention SP == single device (VERDICT next-round #7)."""
    base = _train({"n": 1})
    sp = {f"attention_{i}": ParallelConfig(dims=(2, 4, 1),
                                           device_ids=tuple(range(8)))
          for i in range(2)}
    dpsp = _train({"n": 2, "s": 4}, sp)
    np.testing.assert_allclose(base, dpsp, rtol=2e-4, atol=2e-4)


def test_transformer_causal_dp_sp_parity():
    """Causal masking must agree across the ring's block boundaries."""
    base = _train({"n": 1}, causal=True)
    sp = {f"attention_{i}": ParallelConfig(dims=(1, 8, 1),
                                           device_ids=tuple(range(8)))
          for i in range(2)}
    spo = _train({"s": 8}, sp, causal=True)
    np.testing.assert_allclose(base, spo, rtol=2e-4, atol=2e-4)


def test_transformer_tp_parity():
    """Head/FFN tensor parallelism over 'c' == single device."""
    base = _train({"n": 1})
    tp = {}
    for i in range(2):
        tp[f"attention_{i}"] = ParallelConfig(dims=(2, 1, 4),
                                              device_ids=tuple(range(8)))
        tp[f"ffn_up_{i}"] = ParallelConfig(dims=(2, 1, 4),
                                           device_ids=tuple(range(8)))
    dptp = _train({"n": 2, "c": 4}, tp)
    np.testing.assert_allclose(base, dptp, rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_dense():
    """Direct kernel check: ring online-softmax == dense softmax attention,
    both causal and not, including gradients."""
    mesh = MachineMesh({"s": 4})
    rng = np.random.default_rng(1)
    q, k, v = (rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
               for _ in range(3))
    for causal in (False, True):
        dense = _dense_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), causal, 0.35, 0.0, None)
        ring = ring_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              mesh, causal, 0.35)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                                   rtol=1e-5, atol=1e-5)

        def loss_dense(q):
            return jnp.sum(_dense_attention(q, jnp.asarray(k), jnp.asarray(v),
                                            causal, 0.35, 0.0, None) ** 2)

        def loss_ring(q):
            return jnp.sum(ring_attention(q, jnp.asarray(k), jnp.asarray(v),
                                          mesh, causal, 0.35) ** 2)

        gd = jax.grad(loss_dense)(jnp.asarray(q))
        gr = jax.grad(loss_ring)(jnp.asarray(q))
        np.testing.assert_allclose(np.asarray(gd), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_nondivisible_batch_degrades():
    """Batch not divisible by the n axis must fall back to a replicated
    batch spec inside the ring, not crash at trace time."""
    cfg = ff.FFConfig(batch_size=6, compute_dtype="float32")
    cfg.strategies = {"attention_0": ParallelConfig(
        dims=(1, 2, 1), device_ids=(0, 1))}
    model, tokens, logits = build_transformer(
        cfg, num_layers=1, d_model=32, num_heads=2, d_ff=64, seq_len=8,
        vocab_size=50, num_classes=4)
    model.compile(ff.SGDOptimizer(lr=0.05),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [],
                  final_tensor=logits, mesh=MachineMesh({"n": 4, "s": 2}))
    model.init_layers(seed=0)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 50, (6, 8)).astype(np.int32)
    y = rng.integers(0, 4, (6, 1)).astype(np.int32)
    assert np.isfinite(float(model.train_batch(x, y)))


def test_ring_attention_dropout_trains():
    """The ring path must honor attention dropout (masks differ from the
    dense path's RNG stream, so only finiteness + progress are asserted)."""
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    cfg.strategies = {"attention_0": ParallelConfig(
        dims=(1, 8, 1), device_ids=tuple(range(8)))}
    model, tokens, logits = build_transformer(
        cfg, num_layers=1, d_model=32, num_heads=2, d_ff=64, seq_len=16,
        vocab_size=50, num_classes=4, dropout=0.2)
    model.compile(ff.SGDOptimizer(lr=0.05),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [],
                  final_tensor=logits, mesh=MachineMesh({"s": 8}))
    model.init_layers(seed=0)
    x, y = _data(8, 16, 50)
    losses = [float(model.train_batch(x, y)) for _ in range(6)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_flash_attention_flag_degrades_off_tpu():
    """config.flash_attention is an opt-in TPU kernel; on the CPU test
    backend it must silently fall back to the dense path and still train."""
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32",
                      flash_attention=True)
    model, tokens, logits = build_transformer(
        cfg, num_layers=1, d_model=64, num_heads=1, d_ff=64, seq_len=128,
        vocab_size=50, num_classes=4)
    model.compile(ff.SGDOptimizer(lr=0.05),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [],
                  final_tensor=logits)
    model.init_layers(seed=0)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 50, (8, 128)).astype(np.int32)
    y = rng.integers(0, 4, (8, 1)).astype(np.int32)
    assert np.isfinite(float(model.train_batch(x, y)))


def test_searched_transformer_strategy_executes():
    """MCMC search over the transformer graph returns executable strategies
    (extends the round-1 legality property to the attention op)."""
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32",
                      search_budget=40, seed=3)
    model, tokens, logits = build_transformer(
        cfg, num_layers=1, d_model=32, num_heads=2, d_ff=64, seq_len=8,
        vocab_size=50, num_classes=4)
    model.compile(ff.SGDOptimizer(lr=0.05),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY, [],
                  final_tensor=logits)
    model.init_layers(seed=0)
    x, _ = _data(8, 8, 50)
    y = np.zeros((8, 1), np.int32)
    loss = float(model.train_batch(x, y))
    assert np.isfinite(loss)


# ---------------------------------------------------------------------------
# The repo's own flash-attention kernel (ops/flash_kernel.py) on the CPU,
# Pallas in interpret mode: output and the three gradients against the dense
# einsum chain; the shape rule that parts owned kernel, library kernel and
# dense; the trace-time tally ``FFModel.attention_kernels()``.  (That the
# layout wrapper is gone from the COMPILED train step is pinned beside the
# other described-v5e compile, in tests/test_generation.py.)
# ---------------------------------------------------------------------------
def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("s", [256, 1024])          # one block, several
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hd,heads", [(64, 4), (128, 2)])   # pairs, whole
def test_output_and_gradients_match_dense(hd, heads, causal, dtype, s):
    n = 2
    q, k, v, do = (jax.random.normal(key, (n, s, heads * hd),
                                     jnp.float32).astype(dtype)
                   for key in jax.random.split(jax.random.PRNGKey(s + hd), 4))
    scale = 1.0 / np.sqrt(hd)
    assert flash_kernel.supported(heads, hd, s, s)

    def dense(q, k, v):
        q, k, v = (x.astype(jnp.float32).reshape(n, s, heads, hd)
                   for x in (q, k, v))
        return attn_mod._dense_attention(q, k, v, causal, scale, 0.0,
                                         None).reshape(n, s, heads * hd)

    out, vjp = jax.vjp(lambda q, k, v: flash_kernel.flash_attention(
        q, k, v, heads, causal, scale), q, k, v)
    want, vjp_want = jax.vjp(dense, q, k, v)
    assert out.shape == q.shape and out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 6e-3    # bf16 reads 2-3e-3
    assert _rel(out, want) < tol
    for got, ref in zip(vjp(do), vjp_want(do.astype(jnp.float32))):
        assert got.dtype == dtype
        assert _rel(got, ref) < tol


def test_cross_attention_lengths():
    """Queries and keys of different lengths (and block counts)."""
    n, heads, hd, sq, sk = 1, 2, 64, 128, 640
    q, k, v = (jax.random.normal(key, (n, s, heads * hd), jnp.float32)
               for key, s in zip(jax.random.split(jax.random.PRNGKey(3), 3),
                                 (sq, sk, sk)))

    def dense(q, k, v):
        q, k, v = (x.reshape(n, -1, heads, hd) for x in (q, k, v))
        return attn_mod._dense_attention(q, k, v, False, 0.125, 0.0,
                                         None).reshape(n, sq, heads * hd)

    def own(q, k, v):
        return flash_kernel.flash_attention(q, k, v, heads, False, 0.125)

    got = jax.grad(lambda *a: jnp.sum(own(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    assert _rel(own(q, k, v), dense(q, k, v)) < 2e-5
    assert all(_rel(g, r) < 2e-5 for g, r in zip(got, ref))


class _Mesh:
    """Just what ``_shard_axes`` asks of a mesh."""

    is_distributed = True

    def __init__(self, **sizes):
        self.sizes = sizes

    def axis_size(self, axis):
        return self.sizes.get(axis, 1)

    def subaxes(self, axis):
        return (axis,) if self.sizes.get(axis, 1) > 1 else ()


@pytest.mark.parametrize("shape,mesh,flash,core", [
    ((2, 512, 12, 64), None, True, "owned"),
    ((2, 512, 2, 128), None, True, "owned"),
    ((2, 512, 3, 64), None, True, "library"),          # odd heads
    ((2, 512, 4, 32), None, True, "library"),          # no pair fills a lane
    ((2, 576, 4, 64), None, False, None),              # 576 % 128: dense
    ((2, 256, 4, 64), None, False, None),              # under the threshold
    ((4, 512, 12, 64), _Mesh(n=4), True, "owned"),     # data parallel
    ((4, 512, 12, 64), _Mesh(n=2, c=2), True, "owned"),
    ((4, 512, 12, 64), _Mesh(c=4), True, "library"),   # 3 heads a shard
    ((4, 512, 12, 64), _Mesh(c=8), True, "owned"),     # 8 does not divide 12
])
def test_shape_rule(monkeypatch, shape, mesh, flash, core):
    """Owned kernel where the per-shard shape allows it, the library
    kernel for every other shape ``_use_flash`` admits, dense below."""
    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    q = jnp.zeros(shape, jnp.bfloat16)
    assert attn_mod._use_flash(q, q, None, False, training=True) == flash
    if flash:
        assert attn_mod._flash_core(q, q, mesh) == core


def _tiny_encoder(flash, heads=4):
    cfg = ff.FFConfig.parse_args(["-b", "2", "-ll:tpu", "1", "--seed", "0"])
    cfg.flash_attention = flash
    model, _, logits = build_transformer(
        cfg, num_layers=2, d_model=64 * heads, num_heads=heads, d_ff=128,
        seq_len=256, vocab_size=100, num_classes=2)
    model.compile(ff.AdamOptimizer(alpha=1e-3),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.METRICS_ACCURACY], final_tensor=logits)
    model.init_layers(seed=0)
    return model


def test_attention_kernels_tally(monkeypatch):
    """Each attention op notes at trace time which core it lowered to;
    the owned kernel trains a small graph to the losses dense reaches."""
    x = np.random.default_rng(0).integers(0, 100, (2, 256)).astype(np.int32)
    y = np.array([[0], [1]], np.int32)
    zeros = {"owned": 0, "library": 0, "dense": 0, "ring": 0}
    dense = _tiny_encoder(False)
    assert dense.attention_kernels() == zeros          # nothing traced yet
    want = [float(dense.train_batch(x, y)) for _ in range(2)]
    assert dense.attention_kernels() == dict(zeros, dense=2)
    # as on the chip, but the kernel interpreted
    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash_kernel, "_interpret", lambda: True)
    model = _tiny_encoder(True)
    got = [float(model.train_batch(x, y)) for _ in range(2)]
    assert model.attention_kernels() == dict(zeros, owned=2)
    assert model.attention_kernels(training=False) == zeros
    np.testing.assert_allclose(got, want, rtol=0.05)
