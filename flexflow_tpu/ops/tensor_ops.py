"""Shape/layout ops: Flat, Concat, Split, Reshape, Transpose, Dropout, Softmax.

Reference: ``src/ops/{flat,concat,dropout,softmax}.cu``.  The reference's
Flat is a pure ``cudaMemcpyAsync`` (flat.cu); Concat is custom strided
copy/add kernels (concat.cu:205-240); these are all zero/near-zero-cost
reshapes or fused copies under XLA.

Softmax parity note: the reference Softmax backward is an explicit
``input_grad = output_grad`` copy because the loss task computes fused
softmax-cross-entropy gradients (softmax.cu:216-218).  We reproduce that
contract at the loss level instead: sparse-CCE loss consumes *logits* and
uses the numerically-stable fused softmax-CE (see flexflow_tpu/losses.py);
the Softmax op itself is a true softmax with a true autodiff backward.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from ..op import Op, OpContext, OpType
from ..tuned import flag_enabled
from .common import cast_compute


class Flat(Op):
    """4-D (n,c,h,w) -> 2-D (n, c*h*w) (reference flat.cu)."""

    op_type = OpType.FLAT

    def __init__(self, name, input_tensor):
        super().__init__(name, [input_tensor])
        n = input_tensor.shape[0]
        rest = input_tensor.volume // n
        self._add_output((n, rest), input_tensor.dtype)

    def forward(self, params, inputs, ctx):
        x = inputs[0]
        return [x.reshape(x.shape[0], -1)]

    def flops(self):
        return 0


class Reshape(Op):
    op_type = OpType.RESHAPE

    def __init__(self, name, input_tensor, shape):
        super().__init__(name, [input_tensor])
        self._shape = tuple(int(s) for s in shape)
        # a leading dim equal to the graph batch size is batch-RELATIVE:
        # the runtime batch may differ (gradient-accumulation
        # microbatches, fit(batch_size=...) overrides), so reshape
        # preserves whatever leading dim arrives instead of baking the
        # trace-time number in
        self._batch_relative = (
            len(self._shape) > 0
            and input_tensor.num_dims > 0
            and self._shape[0] == input_tensor.shape[0])
        self._add_output(self._shape, input_tensor.dtype)

    def forward(self, params, inputs, ctx):
        shape = self._shape
        if self._batch_relative:
            shape = (inputs[0].shape[0],) + shape[1:]
        return [inputs[0].reshape(shape)]

    def flops(self):
        return 0


class Transpose(Op):
    op_type = OpType.TRANSPOSE

    def __init__(self, name, input_tensor, perm):
        super().__init__(name, [input_tensor])
        self.perm = tuple(perm)
        out_shape = tuple(input_tensor.shape[p] for p in self.perm)
        self._add_output(out_shape, input_tensor.dtype)

    def forward(self, params, inputs, ctx):
        return [jnp.transpose(inputs[0], self.perm)]

    def flops(self):
        return 0


class Concat(Op):
    """Concatenate along ``axis`` (reference concat.cu; keras merge layer)."""

    op_type = OpType.CONCAT

    def __init__(self, name, input_tensors, axis):
        super().__init__(name, list(input_tensors))
        self.axis = axis
        shape = list(input_tensors[0].shape)
        shape[axis] = sum(t.shape[axis] for t in input_tensors)
        self._add_output(tuple(shape), input_tensors[0].dtype)

    def forward(self, params, inputs, ctx):
        dt = jnp.result_type(*[x.dtype for x in inputs])
        xs = [x.astype(dt) for x in inputs]
        # channels-minor path: a channel concat between NHWC-internal
        # convs/pools (inception blocks) concatenates on the LANE axis so
        # the boundary transposes cancel with the neighbors' — the
        # round-5 on-chip attribution charged early-block concat
        # backwards 3-4x their roofline to exactly these relayouts
        if (getattr(ctx, "conv_layout", "nchw") == "nhwc"
                and self.axis == 1 and xs[0].ndim == 4
                and flag_enabled("FF_FAST_CONCAT", "fast_concat")):
            xs = [jnp.transpose(x, (0, 2, 3, 1)) for x in xs]
            y = jnp.concatenate(xs, axis=3)
            return [jnp.transpose(y, (0, 3, 1, 2))]
        return [jnp.concatenate(xs, axis=self.axis)]

    def flops(self):
        return 0


class Split(Op):
    op_type = OpType.SPLIT

    def __init__(self, name, input_tensor, sizes, axis):
        super().__init__(name, [input_tensor])
        self.sizes, self.axis = list(sizes), axis
        for i, s in enumerate(self.sizes):
            shape = list(input_tensor.shape)
            shape[axis] = s
            self._add_output(tuple(shape), input_tensor.dtype, idx=i)

    def forward(self, params, inputs, ctx):
        x = inputs[0]
        outs, start = [], 0
        for s in self.sizes:
            outs.append(jax.lax.slice_in_dim(x, start, start + s, axis=self.axis))
            start += s
        return outs

    def flops(self):
        return 0


class Dropout(Op):
    """Reference dropout.cu (cuDNN dropout with per-part reserve space).
    TPU-native: threefry key split per trace; identity in inference mode."""

    op_type = OpType.DROPOUT
    position_wise = True

    def __init__(self, name, input_tensor, rate, seed=0):
        super().__init__(name, [input_tensor])
        self.rate, self.seed = float(rate), seed
        self._add_output(input_tensor.shape, input_tensor.dtype)

    def forward(self, params, inputs, ctx: OpContext):
        x = inputs[0]
        if not ctx.training or self.rate <= 0.0:
            return [x]
        key = jax.random.fold_in(ctx.rng, self.outputs[0].uid)
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(key, keep, x.shape)
        return [jnp.where(mask, x / keep, jnp.zeros_like(x))]

    def parallel_dims(self):
        return (True,) * self.outputs[0].num_dims

    def flops(self):
        return self.outputs[0].volume


class Softmax(Op):
    """Reference softmax.cu (cudnnSoftmaxForward ACCURATE, sample-parallel)."""

    op_type = OpType.SOFTMAX
    position_wise = True

    def __init__(self, name, input_tensor, axis=-1):
        super().__init__(name, [input_tensor])
        self.axis = axis
        self._add_output(input_tensor.shape, input_tensor.dtype)

    def forward(self, params, inputs, ctx):
        # f32 for the reduction: ACCURATE-mode parity
        y = jax.nn.softmax(inputs[0].astype(jnp.float32), axis=self.axis)
        return [cast_compute(y, ctx)]

    def parallel_dims(self):
        nd = self.outputs[0].num_dims
        return (True,) + (False,) * (nd - 1)

    def flops(self):
        return 4 * self.outputs[0].volume
