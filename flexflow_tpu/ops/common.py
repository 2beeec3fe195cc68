"""Shared op helpers: dtype policy and activation epilogues.

MXU policy: matmuls/convs run in the configured compute dtype (bfloat16 by
default) with float32 accumulation (``preferred_element_type``); parameters
stay float32.  The reference's analogue is cuDNN/cuBLAS float32 throughout —
bf16+f32-accumulate is the TPU-native equivalent contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import PRECISION_DTYPES

# graph-metadata dtype names, so op modules never spell a raw dtype
# string (repo_lint RL012: dtype resolution lives HERE, nowhere else
# under flexflow_tpu/ops/)
F32 = "float32"
BF16 = "bfloat16"


def resolve_op_dtype(op, base_dtype: str) -> str:
    """THE per-op compute-dtype resolution point (ISSUE 14): an op runs
    in its strategy's ``ParallelConfig.precision`` override when one is
    set ("bf16"/"f32"), else in the session dtype ``base_dtype``
    (``FFConfig.compute_dtype``).  ``FFModel._run_ops`` calls this once
    per op and installs the result as ``ctx.compute_dtype`` before the
    op's forward runs, so every ``cast_compute`` site — and nothing
    else — sees the resolved dtype.  With no overrides the result is
    ``base_dtype`` for every op: traced programs are bit-identical to a
    build without the precision axis."""
    pc = getattr(op, "parallel_config", None)
    prec = getattr(pc, "precision", "") if pc is not None else ""
    return PRECISION_DTYPES.get(prec, base_dtype)


def dtype_itemsize(dtype) -> int:
    """Byte width of a dtype (object or name) — the one dtype-resolving
    helper op modules may call for size math (RL012)."""
    return jnp.dtype(dtype).itemsize


WIDE = 24           # bits of a wide counter's low word (``add_wide``)


def add_wide(counts, x):
    """``counts`` (.., 2) int32, each a ``[high, low]`` pair in base ``2 **
    WIDE``, plus ``x`` (..,) int32 < 2 ** 30: what an op counts on the
    device for ``stats()`` (sums of live positions) passes 2 ** 31 within
    minutes of serving, and x64 is off."""
    low = counts[..., 1] + x
    return jnp.stack([counts[..., 0] + (low >> WIDE),
                      low & ((1 << WIDE) - 1)], axis=-1)


def read_wide(counts):
    """Wide counters as fetched, ``(.., 2)``, as a list of Python ints."""
    import numpy as np
    wide = np.asarray(counts, np.int64)
    return [int(v) for v in (wide[..., 0] << WIDE) + wide[..., 1]]


def cast_compute(x: jax.Array, ctx) -> jax.Array:
    dt = jnp.dtype(ctx.compute_dtype)
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dt:
        return x.astype(dt)
    return x


def scale_param_name(weight_name: str) -> str:
    """Params-dict key of a quantized weight's per-output-channel scale
    (ONE spelling, shared with serving.quantize which builds the
    entries)."""
    return weight_name + "::scale"


def dequant_matmul(x: jax.Array, q: jax.Array, scale: jax.Array,
                   contract: str) -> jax.Array:
    """Weight-only int8 matmul with the dequantization fused in
    (docs/serving.md "Int8 weight quantization"): ``q`` is the int8
    weight, ``scale`` its per-OUTPUT-channel symmetric scale, and
    ``contract`` the einsum spec whose result's LAST dim is the output
    channel — so ``(x @ (q * scale)) == (x @ q) * scale`` holds exactly
    and the f32 weight never materializes in HBM (XLA fuses the
    int8→compute-dtype convert into the matmul; the resident buffer is
    the int8 tensor plus the (out,) scale vector)."""
    y = jnp.einsum(contract, x, q.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return y * scale.astype(y.dtype)


def apply_activation(x: jax.Array, activation):
    """Fused activation epilogue (reference fuses ReLU into cuDNN conv/linear
    descriptors, conv_2d.cu:343-346; XLA fuses these automatically)."""
    if activation is None or activation == "none":
        return x
    if activation == "relu":
        return jax.nn.relu(x)
    if activation == "sigmoid":
        return jax.nn.sigmoid(x)
    if activation == "tanh":
        return jnp.tanh(x)
    if activation == "elu":
        return jax.nn.elu(x)
    if activation == "gelu":
        return jax.nn.gelu(x)
    if activation == "exp":
        return jnp.exp(x)
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "softmax":
        return jax.nn.softmax(x, axis=-1)
    if callable(activation):
        return activation(x)
    raise ValueError(f"unknown activation {activation!r}")
