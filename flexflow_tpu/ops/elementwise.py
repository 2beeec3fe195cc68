"""ElementUnary / ElementBinary (reference ``src/ops/element_unary.cu``,
``src/ops/element_binary.cu``).

The reference dispatches to cuDNN activation descriptors when possible and
custom CUDA kernels otherwise; XLA fuses all of these into neighbouring ops,
so each is a one-liner here.  Binary ops broadcast (the reference requires
equal shapes; we allow numpy broadcasting as a superset).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..op import Op, OpContext, OpType

_UNARY = {
    "exp": jnp.exp,
    "log": jnp.log,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "elu": jax.nn.elu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "identity": lambda x: x,
    "rsqrt": jax.lax.rsqrt,
    "sqrt": jnp.sqrt,
    "negative": jnp.negative,
}

_BINARY = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "subtract": jnp.subtract,
    "mul": jnp.multiply,
    "multiply": jnp.multiply,
    "div": jnp.divide,
    "divide": jnp.divide,
    "max": jnp.maximum,
    "min": jnp.minimum,
    "pow": jnp.power,
}


class ElementUnary(Op):
    op_type = OpType.ELEMENT_UNARY
    position_wise = True

    def __init__(self, name, input_tensor, fn: str, scalar=None):
        super().__init__(name, [input_tensor])
        if fn not in _UNARY and scalar is None:
            raise ValueError(f"unknown unary op {fn!r}")
        self.fn, self.scalar = fn, scalar
        self._add_output(input_tensor.shape, input_tensor.dtype)

    def forward(self, params, inputs, ctx):
        x = inputs[0]
        if self.scalar is not None:
            s = jnp.asarray(self.scalar, x.dtype)
            if self.fn == "scalar_mul":
                return [x * s]
            if self.fn == "scalar_add":
                return [x + s]
            if self.fn == "scalar_sub":
                return [x - s]
            if self.fn == "scalar_truediv":
                return [x / s]
        return [_UNARY[self.fn](x)]

    def parallel_dims(self):
        return (True,) * self.outputs[0].num_dims

    def flops(self):
        return self.outputs[0].volume


class ElementBinary(Op):
    op_type = OpType.ELEMENT_BINARY
    position_wise = True

    def __init__(self, name, in1, in2, fn: str):
        super().__init__(name, [in1, in2])
        if fn not in _BINARY:
            raise ValueError(f"unknown binary op {fn!r}")
        self.fn = fn
        out_shape = tuple(np.broadcast_shapes(in1.shape, in2.shape))
        self._add_output(out_shape, in1.dtype)

    def forward(self, params, inputs, ctx):
        a, b = inputs
        dt = jnp.result_type(a.dtype, b.dtype)
        return [_BINARY[self.fn](a.astype(dt), b.astype(dt))]

    def parallel_dims(self):
        return (True,) * self.outputs[0].num_dims

    def flops(self):
        return self.outputs[0].volume
