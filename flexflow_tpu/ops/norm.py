"""Normalization ops: BatchNorm (reference ``src/ops/batch_norm.cu``,
CUDNN_BATCHNORM_SPATIAL), plus LayerNorm/RMSNorm (new — required by the
transformer workload BASELINE.json adds; the reference has no attention ops).

BatchNorm state handling: the reference keeps per-partition running stats
inside cuDNN; here running mean/var are non-trainable parameters updated
functionally through ``OpContext.updates`` so the train step stays pure.
Statistics are computed in float32 regardless of compute dtype (matching
cuDNN's double-buffered saved-mean precision).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..initializers import ConstantInitializer, ZeroInitializer
from ..op import Op, OpContext, OpType
from .common import apply_activation, cast_compute


class BatchNorm(Op):
    op_type = OpType.BATCHNORM

    def __init__(self, name, input_tensor, relu=True, momentum=0.9, eps=1e-5):
        super().__init__(name, [input_tensor])
        self.relu, self.momentum, self.eps = relu, momentum, eps
        c = input_tensor.shape[1]
        self._add_output(input_tensor.shape, input_tensor.dtype)
        # scale=1, bias=0 init (reference batch_norm.cu:167-210 init_para_task)
        self.w_scale = self._add_weight((c,), ConstantInitializer(1.0), "scale")
        self.w_bias = self._add_weight((c,), ZeroInitializer(), "bias")
        self.s_mean = self._add_weight((c,), ZeroInitializer(), "running_mean",
                                       trainable=False)
        self.s_var = self._add_weight((c,), ConstantInitializer(1.0),
                                      "running_var", trainable=False)

    def forward(self, params, inputs, ctx: OpContext):
        x = inputs[0]
        xf = x.astype(jnp.float32)
        scale = params[self.w_scale.name]
        bias = params[self.w_bias.name]
        if ctx.training:
            mean = xf.mean(axis=(0, 2, 3))
            var = xf.var(axis=(0, 2, 3))
            m = self.momentum
            ctx.updates[self.s_mean.name] = (
                m * params[self.s_mean.name] + (1 - m) * mean)
            ctx.updates[self.s_var.name] = (
                m * params[self.s_var.name] + (1 - m) * var)
        else:
            mean = params[self.s_mean.name]
            var = params[self.s_var.name]
        inv = jax.lax.rsqrt(var + self.eps) * scale
        y = (xf - mean.reshape(1, -1, 1, 1)) * inv.reshape(1, -1, 1, 1) \
            + bias.reshape(1, -1, 1, 1)
        if self.relu:
            y = jax.nn.relu(y)
        return [cast_compute(y, ctx)]

    def parallel_dims(self):
        return (True, False, True, True)

    def flops(self):
        return 8 * self.outputs[0].volume

    def internal_io_bytes(self, flash_attention=None):
        # f32 promotion + cross-sample stats pass + normalize re-read:
        # ~10 B/element beyond the boundary tensors (calibrated: bn35
        # measured 0.70ms fwd vs 0.20ms analytic without this term)
        return 10 * self.inputs[0].volume


class LayerNorm(Op):
    op_type = OpType.LAYERNORM
    position_wise = True

    def __init__(self, name, input_tensor, eps=1e-5, use_scale=True,
                 use_bias=True):
        super().__init__(name, [input_tensor])
        self.eps = eps
        d = input_tensor.shape[-1]
        self._add_output(input_tensor.shape, input_tensor.dtype)
        self.w_scale = (self._add_weight((d,), ConstantInitializer(1.0), "scale")
                        if use_scale else None)
        self.w_bias = (self._add_weight((d,), ZeroInitializer(), "bias")
                       if use_bias else None)

    def forward(self, params, inputs, ctx: OpContext):
        x = inputs[0]
        if self.w_scale is not None and self.w_bias is not None:
            # fused single-pass Pallas kernel (ops/pallas_norm.py):
            # default OFF behind the tuned-table gate and a VMEM
            # bound; bit-parity with the stock path below is
            # pinned in tests/test_pallas_norm.py
            from .pallas_norm import (fused_layernorm, supported,
                                      use_pallas_norm)
            if use_pallas_norm() and supported(x.shape, x.dtype):
                y = fused_layernorm(x, None, params[self.w_scale.name],
                                    params[self.w_bias.name], self.eps)
                return [cast_compute(y, ctx)]
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=-1, keepdims=True)
        var = xf.var(axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        if self.w_scale is not None:
            y = y * params[self.w_scale.name]
        if self.w_bias is not None:
            y = y + params[self.w_bias.name]
        return [cast_compute(y, ctx)]

    def parallel_dims(self):
        nd = self.outputs[0].num_dims
        return (True,) * (nd - 1) + (False,)

    def flops(self):
        return 8 * self.outputs[0].volume

    def internal_io_bytes(self, flash_attention=None):
        # f32 promotion + per-row stats pass (last-axis reduction is
        # cheaper than batchnorm's cross-sample pass)
        return 8 * self.inputs[0].volume


def rms_normalize(x, scale, eps):
    """``x`` over its last axis's root mean square, times ``scale``; f32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + eps) * scale


class RMSNorm(Op):
    op_type = OpType.RMSNORM
    position_wise = True

    def __init__(self, name, input_tensor, eps=1e-6):
        super().__init__(name, [input_tensor])
        self.eps = eps
        d = input_tensor.shape[-1]
        self._add_output(input_tensor.shape, input_tensor.dtype)
        self.w_scale = self._add_weight((d,), ConstantInitializer(1.0), "scale")

    def forward(self, params, inputs, ctx: OpContext):
        return [cast_compute(rms_normalize(
            inputs[0], params[self.w_scale.name], self.eps), ctx)]

    def parallel_dims(self):
        nd = self.outputs[0].num_dims
        return (True,) * (nd - 1) + (False,)

    def flops(self):
        return 4 * self.outputs[0].volume

    def internal_io_bytes(self, flash_attention=None):
        return 8 * self.inputs[0].volume
