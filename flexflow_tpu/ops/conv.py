"""Conv2D / Pool2D (reference ``src/ops/conv_2d.cu``, ``src/ops/pool_2d.cu``).

The reference wraps cuDNN with autotuned algorithms and optional fused ReLU
(conv_2d.cu:343-346, 413-417).  Here Conv2D is a single
``lax.conv_general_dilated`` — XLA tiles it onto the MXU and fuses the bias
add + activation epilogue, so the cuDNN "fused relu" path is the default
compiled behaviour, not a special case.  Backward comes from autodiff (the
reference's bwdFilter/bwdData algorithm selection is XLA's job).

Parallelism: the reference allows 4-D (n,h,w) partitions but asserts
``num_par_c == 1`` (conv_2d.cu:201).  We declare n/h/w splittable —
GSPMD implements the h/w (attribute) splits with automatic halo exchange,
replacing the reference's reliance on Legion moving overlapping partition
rects.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..initializers import GlorotUniform, ZeroInitializer
from ..op import Op, OpContext, OpType
from ..tuned import flag_enabled
from .common import apply_activation, cast_compute


# ---------------------------------------------------------------------------
# Fast max-pool: XLA lowers the autodiff backward of reduce_window(max) to
# SelectAndScatter, which serializes badly on TPU — the round-5 on-chip
# attribution charged 27% of Inception's step to pool2d,
# with a single stem pool's backward costing 2.9 ms and its
# forward 3-6x the bandwidth roofline.  This custom_vjp computes BOTH
# directions from k*k strided window slices: forward = elementwise max
# tree, backward = shifted equality-masks (first-match, cuDNN tie
# semantics) scattered through interior-dilated pads — all
# elementwise/VPU work XLA fuses.  FF_FAST_POOL=0 restores the
# reduce_window + autodiff path (chip A/B knob).
# ---------------------------------------------------------------------------

def _dimtuple(base, dh, dw, vh, vw):
    """``base`` with positions ``dh``/``dw`` replaced — the one spot the
    fwd and bwd window arithmetic share."""
    full = list(base)
    full[dh], full[dw] = vh, vw
    return tuple(full)


def _window_slices(xp, kernel, stride, out_hw, spatial):
    """Yield ((i, j), x_ij) for every window offset: x_ij[o] =
    xp[o*s + (i, j)] over the ``spatial`` dims of padded ``xp``.  The
    equality-mask backward is only correct if it compares the EXACT
    slices the forward maxed over, so both directions call this."""
    (kh, kw), (sh, sw), (oh, ow) = kernel, stride, out_hw
    dh, dw = spatial
    for i in range(kh):
        for j in range(kw):
            yield (i, j), lax.slice(
                xp, _dimtuple([0] * xp.ndim, dh, dw, i, j),
                _dimtuple(xp.shape, dh, dw, i + (oh - 1) * sh + 1,
                          j + (ow - 1) * sw + 1),
                _dimtuple([1] * xp.ndim, dh, dw, sh, sw))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _fast_max_pool(x, kernel, stride, padding, spatial):
    """Max pool over the ``spatial`` dims (e.g. (1, 2) for NHWC,
    (2, 3) for NCHW) of a 4-D array.  Forward is an elementwise max
    over the k*k strided window slices — XLA fuses the max tree into
    one pass, where generic ``reduce_window`` measured 3-6x the
    bandwidth roofline on chip (stem pool fwd 1.2 ms vs ~0.2 —
    attested, record removed in PR 21, to be re-measured)."""
    (kh, kw), (sh, sw), (ph, pw) = kernel, stride, padding
    dh, dw = spatial
    h, w = x.shape[dh], x.shape[dw]
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    neg = jnp.array(-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                    else jnp.iinfo(x.dtype).min, x.dtype)
    xp = lax.pad(x, neg, _dimtuple([(0, 0, 0)] * x.ndim, dh, dw,
                                   (ph, ph, 0), (pw, pw, 0)))
    y = None
    for _, x_ij in _window_slices(xp, kernel, stride, (oh, ow), spatial):
        y = x_ij if y is None else jnp.maximum(y, x_ij)
    return y


def _fast_max_pool_fwd(x, kernel, stride, padding, spatial):
    y = _fast_max_pool(x, kernel, stride, padding, spatial)
    return y, (x, y)


def _fast_max_pool_bwd(kernel, stride, padding, spatial, res, g):
    x, y = res
    (kh, kw), (sh, sw), (ph, pw) = kernel, stride, padding
    dh, dw = spatial
    h, w = x.shape[dh], x.shape[dw]
    oh, ow = y.shape[dh], y.shape[dw]
    hp, wp = h + 2 * ph, w + 2 * pw
    neg = jnp.array(-jnp.inf, x.dtype)
    xp = lax.pad(x, neg, _dimtuple([(0, 0, 0)] * x.ndim, dh, dw,
                                   (ph, ph, 0), (pw, pw, 0)))
    grad_p = jnp.zeros(_dimtuple(x.shape, dh, dw, hp, wp), g.dtype)
    claimed = jnp.zeros(y.shape, jnp.bool_)
    zero = jnp.zeros((), g.dtype)
    # the same slices the forward maxed over (bit-exact tie behavior)
    for (i, j), x_ij in _window_slices(xp, kernel, stride, (oh, ow),
                                       spatial):
        m = jnp.logical_and(x_ij == y, jnp.logical_not(claimed))
        claimed = jnp.logical_or(claimed, m)
        contrib = jnp.where(m, g, zero)
        # scatter contrib[o] into grad_p[o*s + (i, j)]: interior
        # dilation by s-1 places outputs on the stride grid, low
        # padding shifts by the offset (first-match mask = cuDNN
        # tie semantics)
        grad_p = grad_p + lax.pad(
            contrib, zero,
            _dimtuple([(0, 0, 0)] * x.ndim, dh, dw,
                      (i, hp - ((oh - 1) * sh + 1) - i, sh - 1),
                      (j, wp - ((ow - 1) * sw + 1) - j, sw - 1)))
    return (lax.slice(grad_p, _dimtuple([0] * x.ndim, dh, dw, ph, pw),
                      _dimtuple(grad_p.shape, dh, dw, ph + h, pw + w)),)


_fast_max_pool.defvjp(_fast_max_pool_fwd, _fast_max_pool_bwd)


def _use_fast_pool() -> bool:
    # Built-in default OFF: on the one real device kind measured so far
    # (TPU v5 lite) the equality-mask VJP lost 6.5x to SelectAndScatter
    # (attested, record removed in PR 21), so unmeasured kinds keep
    # XLA's lowering until a chip run measures a win there (ROADMAP S4).
    return flag_enabled("FF_FAST_POOL", "fast_pool", default=False)


# ---------------------------------------------------------------------------
# Phase-decomposed stride-s data gradient.  XLA computes the dgrad of a
# strided conv as a conv over the INTERIOR-DILATED incoming gradient
# (s-1 zeros between rows/cols) — at stride 2 that wastes ~3/4 of the
# MACs, and the round-5 calibration measured stem stride-2 convs at
# 2.6x their roofline fwd+bwd (BASELINE.md).  Decomposing by input-
# position parity turns the dgrad into s*s dense STRIDE-1 convs of the
# un-dilated gradient with the filter taps of matching parity — the
# exact same useful FLOPs, zero waste, all MXU-friendly.  The filter
# gradient keeps XLA's standard path.  Both layouts (`nhwc` static arg;
# NHWC/HWIO or NCHW/OIHW); FF_FAST_DGRAD=0 restores autodiff.
# ---------------------------------------------------------------------------

def _conv_dn(nhwc: bool):
    return ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_fast_dgrad(x, w, stride, padding, nhwc):
    """conv_general_dilated with a phase-decomposed dgrad."""
    return lax.conv_general_dilated(
        x, w, window_strides=stride,
        padding=[(padding[0], padding[0]), (padding[1], padding[1])],
        dimension_numbers=_conv_dn(nhwc))


def _conv_fast_dgrad_fwd(x, w, stride, padding, nhwc):
    y = _conv_fast_dgrad(x, w, stride, padding, nhwc)
    return y, (x, w)


def _phase_dgrad(dy, w, x_shape, stride, padding, nhwc):
    """dx via parity-phase stride-1 convs of dy (both layouts)."""
    if nhwc:
        n, h, wd, cin = x_shape
        kh, kw = w.shape[0], w.shape[1]
        dh, dw_ = 1, 2  # spatial dims of activations
        oh, ow = dy.shape[1], dy.shape[2]
    else:
        n, cin, h, wd = x_shape
        kh, kw = w.shape[2], w.shape[3]
        dh, dw_ = 2, 3
        oh, ow = dy.shape[2], dy.shape[3]
    sh, sw = stride
    ph, pw = padding
    zero = jnp.zeros((), dy.dtype)

    def dimtuple(base, vh, vw):
        full = list(base)
        full[dh], full[dw_] = vh, vw
        return tuple(full)

    out = jnp.zeros((n, h, wd, cin) if nhwc else (n, cin, h, wd),
                    dy.dtype)
    for rh in range(sh):
        for rw in range(sw):
            # taps whose contribution lands on input parity (rh, rw)
            taps_h = [a for a in range(kh) if a % sh == (rh + ph) % sh]
            taps_w = [b for b in range(kw) if b % sw == (rw + pw) % sw]
            hq = (h - rh + sh - 1) // sh  # phase grid extent
            wq = (wd - rw + sw - 1) // sw
            if not taps_h or not taps_w or hq <= 0 or wq <= 0:
                continue
            # phase filter: selected taps, spatially flipped, in/out
            # channels swapped (HWIO with I=cout / OIHW with O=cin)
            if nhwc:
                wp = w[jnp.array(taps_h)][:, jnp.array(taps_w)]
                wp = jnp.transpose(wp[::-1, ::-1], (0, 1, 3, 2))
            else:
                wp = w[:, :, jnp.array(taps_h)][:, :, :, jnp.array(taps_w)]
                wp = jnp.transpose(wp[:, :, ::-1, ::-1], (1, 0, 2, 3))
            # dx[rh + sh*q] = sum_j dy[q - off_j] * wp_j with integer
            # offsets; realized as a VALID stride-1 conv over padded dy
            offs_h = [(a - rh - ph) // sh for a in taps_h]
            offs_w = [(b - rw - pw) // sw for b in taps_w]
            # low pad EXACTLY max(offs) and high pad exactly the VALID-
            # conv remainder — negative values crop (lax.pad edge
            # padding may be negative); clamping to 0 would misalign
            # the flipped taps when every offset is negative
            dyp = lax.pad(dy, zero, dimtuple(
                [(0, 0, 0)] * 4,
                (max(offs_h), hq - 1 - min(offs_h) - (oh - 1), 0),
                (max(offs_w), wq - 1 - min(offs_w) - (ow - 1), 0)))
            dxp = lax.conv_general_dilated(
                dyp, wp, window_strides=(1, 1), padding=[(0, 0), (0, 0)],
                dimension_numbers=_conv_dn(nhwc))
            assert (dxp.shape[dh], dxp.shape[dw_]) == (hq, wq), (
                dxp.shape, hq, wq)
            # interleave onto the (rh::sh, rw::sw) grid via interior-
            # dilated pad (phases are disjoint, so summation interleaves)
            out = out + lax.pad(dxp, zero, dimtuple(
                [(0, 0, 0)] * 4,
                (rh, h - ((hq - 1) * sh + rh) - 1, sh - 1),
                (rw, wd - ((wq - 1) * sw + rw) - 1, sw - 1)))
    return out


def _conv_fast_dgrad_bwd(stride, padding, nhwc, res, g):
    x, w = res
    dx = _phase_dgrad(g, w, x.shape, stride, padding, nhwc)
    # filter grad keeps XLA's standard bwd-filter formulation
    _, w_pullback = jax.vjp(
        lambda ww: lax.conv_general_dilated(
            x, ww, window_strides=stride,
            padding=[(padding[0], padding[0]), (padding[1], padding[1])],
            dimension_numbers=_conv_dn(nhwc)), w)
    (dw,) = w_pullback(g)
    return dx, dw


_conv_fast_dgrad.defvjp(_conv_fast_dgrad_fwd, _conv_fast_dgrad_bwd)


def _use_fast_dgrad() -> bool:
    # Built-in default OFF — measured 2.6x slower than XLA's dilated
    # dgrad on TPU v5 lite (attested, record removed in PR 21); see
    # _use_fast_pool for the tuning story.
    return flag_enabled("FF_FAST_DGRAD", "fast_dgrad", default=False)


class Conv2D(Op):
    op_type = OpType.CONV2D

    def __init__(self, name, input_tensor, out_channels, kernel_h, kernel_w,
                 stride_h, stride_w, padding_h, padding_w, activation=None,
                 use_bias=True, groups=1, kernel_initializer=None,
                 bias_initializer=None):
        super().__init__(name, [input_tensor])
        n, c, h, w = input_tensor.shape
        self.in_channels, self.out_channels = c, out_channels
        self.kernel = (kernel_h, kernel_w)
        self.stride = (stride_h, stride_w)
        self.padding = (padding_h, padding_w)
        self.activation = activation
        self.use_bias = use_bias
        self.groups = groups
        out_h = (h + 2 * padding_h - kernel_h) // stride_h + 1
        out_w = (w + 2 * padding_w - kernel_w) // stride_w + 1
        self._add_output((n, out_channels, out_h, out_w), input_tensor.dtype)
        # weight layout OIHW, matching reference create_conv_weight
        # (model.cc:671-760)
        self.w_kernel = self._add_weight(
            (out_channels, c // groups, kernel_h, kernel_w),
            kernel_initializer or GlorotUniform(), "kernel")
        if use_bias:
            self.w_bias = self._add_weight(
                (out_channels,), bias_initializer or ZeroInitializer(), "bias")

    def forward(self, params, inputs, ctx: OpContext):
        x = cast_compute(inputs[0], ctx)
        k = cast_compute(params[self.w_kernel.name], ctx)
        ph, pw = self.padding
        # "nhwc": channels-minor — the TPU lane dimension (pallas_guide:
        # last dim -> 128 lanes).  Convert at this op's boundary; adjacent
        # conv/pool transposes cancel in XLA, so a conv trunk pays only
        # the graph-edge conversions, and bias/relu fuse as a last-axis
        # epilogue (VERDICT r3 #2 experiment).
        nhwc = ctx.conv_layout == "nhwc"
        if nhwc:
            x = jnp.transpose(x, (0, 2, 3, 1))
            k = jnp.transpose(k, (2, 3, 1, 0))  # OIHW -> HWIO
        # no explicit preferred_element_type: the MXU accumulates bf16 convs
        # in f32 natively, and JAX's conv transpose rule rejects mixed
        # operand/accumulator dtypes in the backward pass
        if (self.groups == 1 and max(self.stride) > 1
                and _use_fast_dgrad()):
            # strided conv: custom VJP replaces the dilated-dgrad
            # lowering with parity-phase stride-1 convs (see
            # _conv_fast_dgrad above)
            y = _conv_fast_dgrad(x, k, self.stride, (ph, pw), nhwc)
        else:
            y = lax.conv_general_dilated(
                x, k, window_strides=self.stride,
                padding=[(ph, ph), (pw, pw)],
                dimension_numbers=(("NHWC", "HWIO", "NHWC") if nhwc
                                   else ("NCHW", "OIHW", "NCHW")),
                feature_group_count=self.groups)
        if self.use_bias:
            b = params[self.w_bias.name].astype(y.dtype)
            y = y + (b if nhwc else b.reshape(1, -1, 1, 1))
        y = apply_activation(y, self.activation)
        if nhwc:
            y = jnp.transpose(y, (0, 3, 1, 2))
        return [cast_compute(y, ctx)]

    def parallel_dims(self):
        # n/h/w splittable, c not (reference conv_2d.cu:201)
        return (True, False, True, True)

    def mxu_efficiency(self):
        # the MXU reduces over in_channels x kernel window; C_in < 8
        # can't fill the reduction lanes (round-5 stem-conv measurement,
        # now seed data: search/calibration_seed.json conv7x7_s2 row)
        return min(1.0, self.in_channels / 8.0)

    def backward_overhead(self, part_degrees=None):
        # strided dgrad lowers to a conv over the interior-dilated
        # gradient, whose MAC waste grows ~s*s (the dilated input is
        # s*s larger with the same nonzero count).  The anchor point is
        # the round-5 conv7x7/s2 measurement — seed CalibrationTable,
        # search/calibration_seed.json, conv2d|128x64x128x128 row: the
        # measured bwd is 3.4x the 2x-forward model while fwd alone
        # matches.  Anchoring the s*s law there: overhead(s) = 1 +
        # 2.4 * s*s / 4, so s=2 reproduces the measured 3.4x and
        # stride-3+ convs scale instead of reusing one constant (ADVICE
        # r5: a flat 3.4x mis-costs stride-3/tiny-kernel convs in
        # analytic search mode).  The seed table's stride-1 conv rows
        # match the 2x-forward model (1.06-1.12x), no correction.
        # Deliberately does NOT consult _use_fast_dgrad():
        # the tuned table never ships fast_dgrad on TPU (microbench: the
        # phase decomposition is 2.6x slower than the dilated lowering
        # there), and on the CPU test backend these TPU-calibrated
        # factors are nominal either way.
        s = max(self.stride)
        return 1.0 + 2.4 * (s * s) / 4.0 if s > 1 else 1.0

    def flops(self):
        n, c_out, oh, ow = self.outputs[0].shape
        kh, kw = self.kernel
        return 2 * n * c_out * oh * ow * (self.in_channels // self.groups) * kh * kw

    def sub_problem(self, part_degrees):
        # the c split shards OIHW filter count (input channels stay full —
        # output-channel parallelism replicates the input, conv_2d.cu); the
        # n/h/w splits shard the input box (halo ignored: one kernel row of
        # overlap is noise next to the tile itself)
        from ..op import pad_degrees
        n, cin, h, w = self.inputs[0].shape
        out = self.outputs[0]
        dn, dc, dh, dw = pad_degrees(part_degrees, 4)
        dims = (dn, dc, dh, dw)
        if n % max(1, dn) or self.out_channels % max(1, dc):
            raise ValueError(f"conv degrees {dims} don't divide")
        if out.shape[2] % max(1, dh) or out.shape[3] % max(1, dw):
            raise ValueError(f"conv spatial degrees {dims} don't divide")
        in_shape = (n // max(1, dn), cin, max(1, h // max(1, dh)),
                    max(1, w // max(1, dw)))
        kh, kw = self.kernel
        shapes = {self.w_kernel.name: (self.out_channels // max(1, dc),
                                       cin // self.groups, kh, kw)}
        if self.use_bias:
            shapes[self.w_bias.name] = (self.out_channels // max(1, dc),)
        return [in_shape], shapes


class Pool2D(Op):
    """Max/avg pooling (reference pool_2d.cu, cuDNN pooling)."""

    op_type = OpType.POOL2D

    def __init__(self, name, input_tensor, kernel_h, kernel_w, stride_h,
                 stride_w, padding_h, padding_w, pool_type="max",
                 activation=None):
        super().__init__(name, [input_tensor])
        n, c, h, w = input_tensor.shape
        self.kernel = (kernel_h, kernel_w)
        self.stride = (stride_h, stride_w)
        self.padding = (padding_h, padding_w)
        self.pool_type = pool_type
        self.activation = activation
        out_h = (h + 2 * padding_h - kernel_h) // stride_h + 1
        out_w = (w + 2 * padding_w - kernel_w) // stride_w + 1
        self._add_output((n, c, out_h, out_w), input_tensor.dtype)

    def forward(self, params, inputs, ctx: OpContext):
        x = cast_compute(inputs[0], ctx)
        ph, pw = self.padding
        if ctx.conv_layout == "nhwc":  # window over dims 1,2; lanes last
            x = jnp.transpose(x, (0, 2, 3, 1))
            spatial = (1, 2)
            window = (1,) + self.kernel + (1,)
            strides = (1,) + self.stride + (1,)
            padding = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        else:
            spatial = (2, 3)
            window = (1, 1) + self.kernel
            strides = (1, 1) + self.stride
            padding = ((0, 0), (0, 0), (ph, ph), (pw, pw))
        if self.pool_type == "max":
            y = None
            if _use_fast_pool() and jnp.issubdtype(x.dtype, jnp.floating):
                y = _fast_max_pool(x, self.kernel, self.stride,
                                   self.padding, spatial)
            if y is None:
                init = (-jnp.inf
                        if jnp.issubdtype(x.dtype, jnp.floating)
                        else jnp.iinfo(x.dtype).min)
                y = lax.reduce_window(x, init, lax.max, window, strides,
                                      padding)
        else:
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
            y = s / (self.kernel[0] * self.kernel[1])
        y = apply_activation(y, self.activation)
        if ctx.conv_layout == "nhwc":
            y = jnp.transpose(y, (0, 3, 1, 2))
        return [y]

    def parallel_dims(self):
        return (True, False, True, True)

    def flops(self):
        return self.outputs[0].volume * self.kernel[0] * self.kernel[1]

    def backward_overhead(self, part_degrees=None):
        # max-pool backward lowers to SelectAndScatter: the round-5
        # pool2x2 measurement (seed CalibrationTable,
        # search/calibration_seed.json pool2d row) put it at 1.9x its
        # bandwidth roofline; avg-pool backward is a plain dilated sum,
        # on roofline
        return 1.9 if self.pool_type == "max" else 1.0
