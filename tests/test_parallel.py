"""Distributed tests on the virtual 8-device CPU mesh: DP, TP, strategy
-driven sharding, and parity between 1-chip and 8-chip results."""

import jax
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import ParallelConfig
from flexflow_tpu.parallel.mesh import MachineMesh, dim_axis_names
from flexflow_tpu.parallel.sharding import output_spec, param_spec


def test_virtual_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_mesh_construction():
    m = MachineMesh({"n": 4, "c": 2})
    assert m.num_devices == 8
    assert m.axis_size("n") == 4
    assert m.axis_size("model") == 2
    m1 = MachineMesh({"n": 1})
    assert not m1.is_distributed


def test_dim_axis_names():
    assert dim_axis_names(4) == ("n", "c", "h", "w")
    assert dim_axis_names(3) == ("n", "s", "c")
    assert dim_axis_names(2) == ("n", "c")


def build_mlp(cfg, mesh=None):
    model = ff.FFModel(cfg, mesh=mesh)
    x = model.create_tensor((cfg.batch_size, 16), name="x")
    t = model.dense(x, 64, activation="relu")
    t = model.dense(t, 8)
    return model, t


def _train(model, logits, x, y, steps=5, lr=0.05):
    model.compile(ff.SGDOptimizer(lr=lr), "sparse_categorical_crossentropy",
                  [ff.METRICS_ACCURACY], final_tensor=logits)
    model.init_layers(seed=0)
    losses = [float(model.train_batch(x, y)) for _ in range(steps)]
    return losses, {k: np.asarray(v) for k, v in model._params.items()}


def test_dp_matches_single_device():
    """8-way data parallel must be numerically equivalent to 1 device
    (the psum gradient reduction == reference replica-sum,
    optimizer_kernel.cu:168-179)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 16), dtype=np.float32)
    y = rng.integers(0, 8, (32, 1)).astype(np.int32)
    cfg1 = ff.FFConfig(batch_size=32, compute_dtype="float32")
    m1, lg1 = build_mlp(cfg1, MachineMesh({"n": 1}, devices=jax.devices()[:1]))
    l1, p1 = _train(m1, lg1, x, y)
    cfg8 = ff.FFConfig(batch_size=32, compute_dtype="float32")
    cfg8.strategies = {"dense": ParallelConfig.data_parallel(8, 2),
                       "dense_1": ParallelConfig.data_parallel(8, 2)}
    m8, lg8 = build_mlp(cfg8, MachineMesh({"n": 8}))
    l8, p8 = _train(m8, lg8, x, y)
    np.testing.assert_allclose(l1, l8, rtol=1e-4, atol=1e-5)
    for k in p1:
        np.testing.assert_allclose(p1[k], p8[k], rtol=1e-4, atol=1e-5)


def test_tp_matches_single_device():
    """Tensor parallel (channel split on dense layers) == single device.
    The reference's Linear replica-reduce path (linear.cu:592-619) is
    GSPMD's psum here."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((16, 16), dtype=np.float32)
    y = rng.integers(0, 8, (16, 1)).astype(np.int32)
    cfg1 = ff.FFConfig(batch_size=16, compute_dtype="float32")
    m1, lg1 = build_mlp(cfg1, MachineMesh({"n": 1}, devices=jax.devices()[:1]))
    l1, p1 = _train(m1, lg1, x, y)

    cfgt = ff.FFConfig(batch_size=16, compute_dtype="float32")
    cfgt.strategies = {
        "dense": ParallelConfig(dims=(2, 4), device_ids=tuple(range(8))),
        "dense_1": ParallelConfig(dims=(2, 4), device_ids=tuple(range(8))),
    }
    mt, lgt = build_mlp(cfgt, MachineMesh({"n": 2, "c": 4}))
    lt, pt = _train(mt, lgt, x, y)
    np.testing.assert_allclose(l1, lt, rtol=1e-4, atol=1e-5)
    for k in p1:
        np.testing.assert_allclose(p1[k], pt[k], rtol=1e-4, atol=1e-5)


def test_param_sharding_placement():
    """TP weights must actually be sharded across the 'c' axis."""
    cfg = ff.FFConfig(batch_size=16, compute_dtype="float32")
    cfg.strategies = {
        "dense": ParallelConfig(dims=(1, 8), device_ids=tuple(range(8))),
    }
    mesh = MachineMesh({"c": 8})
    model = ff.FFModel(cfg, mesh=mesh)
    x = model.create_tensor((16, 16), name="x")
    t = model.dense(x, 64, activation="relu")
    model.compile(ff.SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
                  [], final_tensor=t)
    model.init_layers()
    kernel = model._params["dense/kernel"]
    # 64x16 kernel sharded on dim 0 over 8 devices -> 8x16 per shard
    shard_shapes = {s.data.shape for s in kernel.addressable_shards}
    assert shard_shapes == {(8, 16)}


def test_conv_spatial_split_runs():
    """SOAP attribute (h/w) parallelism: GSPMD halo exchange for convs."""
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32")
    cfg.strategies = {
        "conv2d": ParallelConfig(dims=(2, 1, 2, 2),
                                 device_ids=tuple(range(8))),
    }
    mesh = MachineMesh({"n": 2, "h": 2, "w": 2})
    model = ff.FFModel(cfg, mesh=mesh)
    x = model.create_tensor((4, 3, 16, 16), name="img")
    t = model.conv2d(x, 8, 3, 3, 1, 1, 1, 1, activation="relu")
    t = model.flat(t)
    t = model.dense(t, 4)
    model.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
                  [], final_tensor=t)
    model.init_layers()
    rng = np.random.default_rng(0)
    xd = rng.standard_normal((4, 3, 16, 16), dtype=np.float32)
    yd = rng.integers(0, 4, (4, 1)).astype(np.int32)
    loss = float(model.train_batch(xd, yd))
    assert np.isfinite(loss)


def test_output_spec_mesh_expressibility():
    mesh = MachineMesh({"n": 4, "c": 2})
    from flexflow_tpu.tensor import Tensor
    t = Tensor((32, 64))
    spec = output_spec(t, ParallelConfig(dims=(4, 2),
                                         device_ids=tuple(range(8))), mesh)
    assert tuple(spec) == ("n", "c")
    # mixed degree < axis size maps onto a prime sub-axis subset
    spec = output_spec(t, ParallelConfig(dims=(2, 2),
                                         device_ids=tuple(range(4))), mesh)
    # sub-axis subset of the n axis (PartitionSpec normalises the
    # 1-tuple ("n0",) to the bare name)
    assert tuple(spec) == ("n0", "c")
    # a non-divisor degree degrades to replication, RECORDED as an
    # aggregated verifier diagnostic (FF106) instead of one warning per
    # traced tensor (ISSUE 3)
    from flexflow_tpu.analysis import drain_replicate_fallbacks
    drain_replicate_fallbacks()  # clear prior traces
    t3 = Tensor((30, 64), name="t3")
    spec = output_spec(t3, ParallelConfig(dims=(3, 1),
                                          device_ids=(0, 1, 2)), mesh)
    assert tuple(spec) == (None, None)
    diags = drain_replicate_fallbacks()
    assert [d.code for d in diags] == ["FF106"]
    assert "degree 3" in diags[0].message
    assert diags[0].op == "t3"
    assert drain_replicate_fallbacks() == []  # drained


def test_mixed_degree_strategy_executes():
    """The VERDICT repro: conv (4,1,1,1) + dense (8,1) in one model used to
    crash at trace time (Weak#3); sub-axis sharding must run it."""
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32")
    cfg.strategies = {
        "conv2d": ParallelConfig(dims=(4, 1, 1, 1), device_ids=(0, 1, 2, 3)),
        "dense": ParallelConfig(dims=(8, 1), device_ids=tuple(range(8))),
    }
    model = ff.FFModel(cfg, mesh=MachineMesh({"n": 8}))
    x = model.create_tensor((8, 3, 16, 16), name="img")
    t = model.conv2d(x, 8, 3, 3, 1, 1, 1, 1, activation="relu")
    t = model.flat(t)
    t = model.dense(t, 4)
    model.compile(ff.SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
                  [], final_tensor=t)
    model.init_layers()
    rng = np.random.default_rng(0)
    xd = rng.standard_normal((8, 3, 16, 16), dtype=np.float32)
    yd = rng.integers(0, 4, (8, 1)).astype(np.int32)
    assert np.isfinite(float(model.train_batch(xd, yd)))
