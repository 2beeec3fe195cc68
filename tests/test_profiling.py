"""Pins for ``flexflow_tpu.profiling`` — the measurement layer the
calibration subsystem (ISSUE 7) is built on, previously the least-pinned
module in the repo: seeded determinism of the profile inputs, quantile
edge cases, dtype parametrization, the host-side ``time_calls`` timer,
and the slope-mode fencing path."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.linear import Linear
from flexflow_tpu.profiling import (_example_inputs, _init_params,
                                    _nearest_rank, profile_op, quantiles,
                                    time_calls)
from flexflow_tpu.tensor import Tensor


def _dense(shape=(8, 16), out=8, name="fc"):
    return Linear(name, Tensor(shape, name=f"{name}_in"), out)


# ------------------------------------------------------------------
# seeded determinism: the measurement's INPUTS are a pure function of
# the seed (timing itself is wall clock, but what runs must not drift)

def test_example_inputs_seeded_deterministic():
    op = _dense()
    a = _example_inputs(op, seed=0)
    b = _example_inputs(op, seed=0)
    c = _example_inputs(op, seed=1)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_example_inputs_int_tensors_are_zero_indices():
    ids = Tensor((4, 2), dtype="int32", name="ids")
    from flexflow_tpu.ops.linear import Embedding
    op = Embedding("emb", ids, 16, 4)
    (x,) = _example_inputs(op)
    assert x.dtype == jnp.int32 and int(jnp.max(jnp.abs(x))) == 0


def test_example_inputs_shape_override():
    op = _dense(shape=(8, 16))
    (x,) = _example_inputs(op, shapes=[(2, 16)])
    assert x.shape == (2, 16)  # measure mode's per-partition sub-shape


def test_init_params_seeded_deterministic():
    op = _dense()
    p1 = _init_params(op, seed=0)
    p2 = _init_params(op, seed=0)
    for k in p1:
        np.testing.assert_array_equal(np.asarray(p1[k]),
                                      np.asarray(p2[k]))


# ------------------------------------------------------------------
# quantiles: nearest-rank edge cases

def test_quantiles_empty_is_nan():
    q = quantiles([])
    assert set(q) == {0.5, 0.95, 0.99}
    assert all(v != v for v in q.values())


def test_quantiles_single_sample_every_q():
    q = quantiles([7.25], qs=(0.01, 0.5, 0.99))
    assert all(v == 7.25 for v in q.values())


def test_quantiles_nearest_rank_exact():
    xs = list(range(1, 21))  # 1..20
    q = quantiles(xs, qs=(0.5, 0.95, 0.99))
    # nearest-rank: ceil(q*n) -> p50 = 10th value, p95 = 19th, p99 = 20th
    assert q[0.5] == 10 and q[0.95] == 19 and q[0.99] == 20
    # every reported value actually occurred
    assert all(v in xs for v in q.values())


def test_quantiles_unsorted_input():
    assert quantiles([3, 1, 2], qs=(0.5,))[0.5] == 2


def test_nearest_rank_no_float_jitter():
    # 0.95 * 20 == 18.999...96 in floats; exact arithmetic must still
    # land on rank ceil(19) - 1 = 18
    assert _nearest_rank(0.95, 20) == 18
    assert _nearest_rank(0.5, 1) == 0
    assert _nearest_rank(0.99, 100) == 98


# ------------------------------------------------------------------
# time_calls: the host-side search-throughput timer

def test_time_calls_accumulates_min_time():
    calls = []
    cps, n = time_calls(lambda: calls.append(1), min_time_s=0.02)
    assert n == len(calls) >= 1
    assert cps > 0 and math.isfinite(cps)


def test_time_calls_respects_max_calls():
    cps, n = time_calls(lambda: None, min_time_s=10.0, max_calls=5)
    assert n == 5


# ------------------------------------------------------------------
# profile_op: dtype parametrization + slope-mode fencing

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_profile_op_dtypes_finite(dtype):
    # finite and non-negative (the two-point slope clamps at 0.0 when
    # host jitter exceeds a microsecond-scale op; NaN would mean the
    # timing loop itself failed)
    r = profile_op(_dense(), compute_dtype=dtype, warmup=1, iters=2)
    assert math.isfinite(r["fwd_ms"]) and r["fwd_ms"] >= 0
    assert math.isfinite(r["bwd_ms"]) and r["bwd_ms"] >= 0


def test_profile_op_sub_shapes():
    op = _dense(shape=(8, 16))
    r = profile_op(op, compute_dtype="float32", warmup=1, iters=2,
                   input_shapes=[(4, 16)])
    assert math.isfinite(r["fwd_ms"])


def test_slope_mode_nan_survives_failed_backward():
    # ops with no differentiable path report NaN bwd, never 0.0 (a
    # free backward would poison the calibration table silently)
    from flexflow_tpu.ops.tensor_ops import Reshape
    ids = Tensor((4, 8), dtype="int32", name="ids")
    r = profile_op(Reshape("rs", ids, (8, 4)), warmup=1, iters=1)
    assert r["fwd_ms"] != r["fwd_ms"] and r["bwd_ms"] != r["bwd_ms"]
