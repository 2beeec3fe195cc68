"""Pallas TPU fused LayerNorm(+residual) kernel (ISSUE 14 satellite).

Why: in a transformer block the residual add and the following
LayerNorm are two VPU passes over the same activation — XLA usually
fuses the add into the norm's first reduction, but the f32 promotion,
two stat passes and the normalize re-read still stream the tensor
several times (the calibrated ``internal_io_bytes`` of
``ops/norm.LayerNorm`` charges ~8 B/element beyond the boundary
tensors).  This kernel holds a block of rows in VMEM and performs
add + mean/var + normalize + affine in ONE pass: HBM sees one read of
x (and the residual) and one write of y.

Same statistics, same order, as the stock path (``ops/norm.LayerNorm``
/ the pipeline block's ``ln``): promote to f32, ``mean``/``var`` over
the last axis, ``rsqrt(var + eps)``, scale/bias — parity is pinned in
tests/test_pallas_norm.py.  The backward recomputes through the plain
jnp reference under ``jax.vjp`` (the forward's win is bandwidth; the
backward keeps autodiff-exact gradients).

Gating: ``FF_PALLAS_NORM`` env  >  tuned-table key ``pallas_norm`` (per
device kind, committed once ``scripts/kernel_microbench.py`` measures a
win there — ROADMAP S4)  >  built-in OFF.  ``supported()`` additionally
bounds the per-tile VMEM working set (``FF_PALLAS_NORM_VMEM``, under the
16 MiB scoped-VMEM default Mosaic applies) and requires a whole-row
tiling.  Compiled by Mosaic and compared with the reference on TPU v5
lite at 16 384 x 768 bf16 rows by chip_smoke.py (PR 21); whether it is
FASTER there is not measured.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..tuned import flag_enabled
from .common import dtype_itemsize

# per-core VMEM ceiling for one (rows-block, features) tile: x, res,
# f32 working copy, y plus reduction temporaries — ~6 live row-blocks
_VMEM_BUDGET = int(os.environ.get("FF_PALLAS_NORM_VMEM",
                                  12 * 1024 * 1024))
_LIVE_FACTOR = 6


def use_pallas_norm() -> bool:
    """Env > tuned table (device kind) > built-in OFF (enable per
    device kind only after a chip run measures a win there)."""
    return flag_enabled("FF_PALLAS_NORM", "pallas_norm", default=False)


def _interpret() -> bool:
    """Interpret mode is for the CPU (tests) only; on any other
    platform the kernel is compiled by Mosaic or raises."""
    return jax.default_backend() == "cpu"


def _rows(shape) -> int:
    r = 1
    for s in shape[:-1]:
        r *= int(s)
    return r


def _row_block(nrows: int, d: int, itemsize: int) -> int:
    """Largest divisor of ``nrows`` whose tile fits the VMEM budget
    (whole blocks only — no ragged-edge masking in the kernel)."""
    per_row = d * max(itemsize, 4) * _LIVE_FACTOR
    cap = max(1, _VMEM_BUDGET // max(1, per_row))
    best = 1
    for rb in range(1, nrows + 1):
        if nrows % rb == 0 and rb <= cap:
            best = rb
    return best


def supported(x_shape, dtype) -> bool:
    """Static go/no-go: floating input of rank >= 2, and one full row
    (feature dim) fits the VMEM budget."""
    if len(x_shape) < 2 or not jnp.issubdtype(dtype, jnp.floating):
        return False
    d = int(x_shape[-1])
    if d <= 0 or _rows(x_shape) <= 0:
        return False
    return d * max(dtype_itemsize(dtype), 4) * _LIVE_FACTOR \
        <= _VMEM_BUDGET


def _ln_reference(x, res, scale, bias, eps):
    """The stock math (ops/norm.LayerNorm with the residual folded in)
    — the parity anchor AND the backward's recompute path."""
    xf = x.astype(jnp.float32)
    if res is not None:
        xf = xf + res.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y * scale + bias


def _ln_kernel(x_ref, s_ref, b_ref, y_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y_ref[...] = y * s_ref[...] + b_ref[...]


def _ln_res_kernel(x_ref, r_ref, s_ref, b_ref, y_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = xf.var(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y_ref[...] = y * s_ref[...] + b_ref[...]


def _compiler_params():
    if _interpret():
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def _call(kern, args, nrows, d, out_dtype):
    import jax.experimental.pallas as pl

    rb = _row_block(nrows, d, dtype_itemsize(args[0].dtype))
    grid = (nrows // rb,)
    row_spec = pl.BlockSpec((rb, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((d,), lambda i: (0,))
    n_rows_args = len(args) - 2  # trailing two are scale/bias vectors
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row_spec] * n_rows_args + [vec_spec, vec_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((nrows, d), out_dtype),
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_layernorm(x, res, scale, bias, eps):
    """LayerNorm(x [+ res]) * scale + bias as ONE Pallas pass, f32
    statistics, f32 output (matching the stock op, which casts back to
    the compute dtype at its own boundary).  ``res=None`` runs the
    plain-norm variant.  Caller must check :func:`supported` (and the
    :func:`use_pallas_norm` gate)."""
    d = int(x.shape[-1])
    nrows = _rows(x.shape)
    x2 = x.reshape(nrows, d)
    if res is None:
        y = _call(functools.partial(_ln_kernel, eps=eps),
                  (x2, scale, bias), nrows, d, jnp.float32)
    else:
        y = _call(functools.partial(_ln_res_kernel, eps=eps),
                  (x2, res.reshape(nrows, d), scale, bias),
                  nrows, d, jnp.float32)
    return y.reshape(x.shape[:-1] + (d,))


def _fused_fwd(x, res, scale, bias, eps):
    return fused_layernorm(x, res, scale, bias, eps), (x, res, scale, bias)


def _fused_bwd(eps, saved, g):
    x, res, scale, bias = saved
    if res is None:
        _, vjp = jax.vjp(
            lambda xx, s, b: _ln_reference(xx, None, s, b, eps),
            x, scale, bias)
        dx, ds, db = vjp(g)
        return dx, None, ds, db
    _, vjp = jax.vjp(
        lambda xx, rr, s, b: _ln_reference(xx, rr, s, b, eps),
        x, res, scale, bias)
    return vjp(g)


fused_layernorm.defvjp(_fused_fwd, _fused_bwd)


__all__ = ["fused_layernorm", "supported", "use_pallas_norm",
           "_ln_reference"]
