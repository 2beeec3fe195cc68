"""The repo's own Pallas flash attention for TPU, forward + backward under
one ``jax.custom_vjp``, built for the layout the projections produce.

Operands are ``(n, s, h * hd)`` — a token's heads side by side in ONE
lane-dense minor dim, exactly what ``MultiHeadAttention._qkv`` computes
before its unfold and what ``_out_proj`` folds back to first thing — so no
``(n,s,h,d) <-> (n,h,s,d)`` transpose stands on either side, forward or
backward, and every HBM tile is full.  At ``hd == 64`` a block is
``(1, bq, 128)``: TWO heads fill the 128 lanes and the kernel works on each
in turn WITHOUT slicing lanes — the other head's half of ``q`` (or ``do``)
is zeroed, so the MXU contracts over all 128 lanes at the cost a 64-deep
contraction has on a 128-deep array anyway, and each head's half of a
128-wide product is selected afterwards.  At ``hd % 128 == 0`` a block is
one head.

Both passes work on TRANSPOSED scores ``k q^T`` (keys along the sublanes),
so a query's statistics are ``(1, bq)`` rows: maxima and sums over the keys
are plain vector ops with no cross-lane step, nothing is a one-lane column,
and the forward saves ONE f32 per query row and head, the log-sum-exp, as
``(n, h / G, G, sq)`` (``G`` heads a block) exactly as the backward
subtracts it.  ``di = rowsum(o * do)`` is formed inside the backward from
the ``o`` and ``do`` blocks it loads anyway.  Nothing of shape
``(n, h, s, 128)`` is ever written to HBM.

In the backward ``dV = P^T dO`` and ``dK = dS^T Q`` are then plain products
and only ``dQ = dS K`` needs one transpose a tile.  Where the keys fit one
block (``sk <= 512``) dQ, dK and dV come out of ONE kernel that recomputes
the scores once (``flash_mha_bwd_fused``); longer key sequences take two
passes (``flash_mha_bwd_dkv``, ``flash_mha_bwd_dq``).  Softmax and
accumulation are f32, the matrix products take the operands' dtype.

Blocks are chosen here from shape (ROADMAP S5 tunes them); the kernels'
``name=`` match ``perfbench/flops``' ``FLASH_KERNELS``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30            # finite, as ops/attention.py: exp() stays NaN-free
LANES = 128
_BLOCKS = (512, 256, 128)
_VMEM_LIMIT = 64 * 1024 * 1024
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def supported(num_heads: int, head_dim: int, sq: int, sk: int) -> bool:
    """Shapes the kernel takes (per shard): two 64-wide heads a block or
    whole heads of a lane multiple; sequence lengths in 128-blocks."""
    lanes_ok = (head_dim % LANES == 0
                or (head_dim == 64 and num_heads % 2 == 0))
    return lanes_ok and sq % LANES == 0 and sk % LANES == 0


def _geometry(q, k, num_heads: int):
    """``(hd, width, bq, bk)``: head size, lanes of a block (two heads at
    head size 64), query and key rows of a block."""
    hd = q.shape[2] // num_heads
    bq, bk = (next(b for b in _BLOCKS if x.shape[1] % b == 0) for x in (q, k))
    return hd, max(hd, LANES), bq, bk


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _params(*semantics):
    if _interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _head_masks(shape, axis: int, hd: int):
    """One mask per head of a block whose dim ``axis`` holds the heads side
    by side; ``[None]`` where the block is one head."""
    if shape[axis] == hd:
        return [None]
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return [(pos >= g * hd) & (pos < (g + 1) * hd)
            for g in range(shape[axis] // hd)]


def _only(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _merge(mask, new, old):
    """``new`` in its head's lanes, ``old`` (earlier heads') elsewhere."""
    return new if mask is None or old is None else jnp.where(mask, new, old)


def _dot(a, b, dims=_NN):
    """f32-accumulated product in the operands' dtype.  bf16 operands go
    to the MXU in one pass whatever ``jax_default_matmul_precision`` says
    (Mosaic lowers no other); f32 operands follow it, as everywhere."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _fold_scale(scale: float) -> bool:
    """A power of two multiplies into ``q`` exactly (1/8 at head size 64),
    which saves a pass over every score tile."""
    return math.frexp(scale)[0] == 0.5


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, hd, scale, causal, nk):
    """Online softmax over TRANSPOSED scores ``k q^T`` (keys along the
    sublanes): the running max and sum of a query are then ``(1, bq)`` rows,
    reduced by plain vector maxima and adds with no cross-lane step, and
    the log-sum-exp comes out in the orientation it is stored in.  The
    accumulator is ``o^T`` (head lanes along the sublanes), transposed once
    when the last key block is done."""
    bq, width = q_ref.shape[1:]
    bk = k_ref.shape[1]
    qi, ki = pl.program_id(2), pl.program_id(3)
    row_masks = _head_masks((width, bq), 0, hd)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step():
        q, k, v_t = q_ref[0], k_ref[0], v_ref[0].T
        fold = _fold_scale(scale)
        if fold:
            q = q * scale
        if causal:
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        for g, (qm, rm) in enumerate(zip(_head_masks((bq, width), 1, hd),
                                         row_masks)):
            s_t = _dot(k, _only(qm, q), _NT)                      # (bk, bq)
            if not fold:
                s_t = s_t * scale
            if causal:
                s_t = jnp.where(kpos > qpos, NEG_INF, s_t)
            m_prev = m_scr[g]                                     # (1, bq)
            m_next = jnp.maximum(m_prev,
                                 jnp.max(s_t, axis=0, keepdims=True))
            p_t = jnp.exp(s_t - m_next)
            alpha = jnp.exp(m_prev - m_next)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p_t, axis=0, keepdims=True)
            m_scr[g] = m_next
            acc = acc_scr[...]                                    # (width, bq)
            acc_scr[...] = _merge(
                rm, acc * alpha + _dot(v_t, p_t.astype(v_t.dtype)), acc)

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(step)
    else:
        step()

    @pl.when(ki == nk - 1)
    def _():
        o_t = None
        for g, rm in enumerate(row_masks):
            l = l_scr[g]
            o_t = _merge(rm, acc_scr[...] * (1.0 / l), o_t)
            lse_ref[0, 0, g:g + 1, :] = m_scr[g] + jnp.log(l)
        o_ref[0] = o_t.T.astype(o_ref.dtype)


# jitted so that the equal-shaped layers of a model share ONE traced and
# lowered kernel (tracing it per layer cost the train cell 3.5 s of set-up)
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _forward(q, k, v, num_heads, causal, scale):
    n, sq, e = q.shape
    hd, width, bq, bk = _geometry(q, k, num_heads)
    g, nk = width // hd, k.shape[1] // bk
    q_spec = pl.BlockSpec((1, bq, width), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((1, bk, width), lambda b, h, i, j: (b, j, h))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hd=hd, scale=scale, causal=causal,
                          nk=nk),
        grid=(n, e // width, sq // bq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, pl.BlockSpec((1, 1, g, bq),
                                        lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, e // width, g, sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, 1, bq), jnp.float32),
                        pltpu.VMEM((g, 1, bq), jnp.float32),
                        pltpu.VMEM((width, bq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=_interpret(), name="flash_attention_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                q_inner, hd, scale, causal):
    """``q_inner``: grid (b, h, ki, qi), dK and dV summed over ``qi``, and
    dQ written too, each step, where ``rest`` leads with its ref (legal only
    when one block holds every key).  Otherwise grid (b, h, qi, ki) and dQ
    alone, summed over ``ki``."""
    bq, width = q_ref.shape[1:]
    bk = k_ref.shape[1]
    if q_inner:
        ki, qi = pl.program_id(2), pl.program_id(3)
        *dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
        accs = ((dk_ref, dk_acc), (dv_ref, dv_acc))
    else:
        qi, ki = pl.program_id(2), pl.program_id(3)
        dq_ref, dq_acc = rest
        accs = ((dq_ref, dq_acc),)

    @pl.when(pl.program_id(3) == 0)
    def _():
        for _, acc in accs:
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def step():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        fold = _fold_scale(scale)
        if fold:
            q = q * scale
        # di = rowsum(o * do) a head, as a row: one small transpose
        od_t = (o_ref[0].astype(jnp.float32) * do.astype(jnp.float32)).T
        if causal:
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        dq = dk = dv = None
        for g, (qm, km) in enumerate(zip(_head_masks((bq, width), 1, hd),
                                         _head_masks((bk, width), 1, hd))):
            lse = lse_ref[0, 0, g:g + 1, :]                       # (1, bq)
            di = jnp.sum(od_t[g * hd:(g + 1) * hd], axis=0, keepdims=True)
            s_t = _dot(k, _only(qm, q), _NT)
            if not fold:
                s_t = s_t * scale
            if causal:
                s_t = jnp.where(kpos > qpos, NEG_INF, s_t)
            p_t = jnp.exp(s_t - lse)                              # (bk, bq)
            ds_t = p_t * (_dot(v, _only(qm, do), _NT) - di)
            if q_inner:
                dv = _merge(km, _dot(p_t.astype(do.dtype), do), dv)
                dk = _merge(km, _dot(ds_t.astype(q.dtype), q), dk)
            if not q_inner or dq_ref:
                dq = _merge(qm, _dot(ds_t.T.astype(k.dtype), k), dq)
        if q_inner:
            dv_acc[...] += dv
            dk_acc[...] += dk if fold else dk * scale
            if dq_ref:
                dq_ref[0][0] = (dq * scale).astype(q_ref.dtype)
        else:
            dq_acc[...] += dq * scale

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(step)
    else:
        step()

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _():
        for ref, acc in accs:
            ref[0] = acc[...].astype(ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _backward(num_heads, causal, scale, res, do):
    q, k, v, o, lse = res
    n, sq, e = q.shape
    hd, width, bq, bk = _geometry(q, k, num_heads)
    nq, nk = sq // bq, k.shape[1] // bk

    def call(name, q_inner, outs):
        """``outs``: which of q's and k's shape the kernel writes."""
        def qi(a, c):
            return c if q_inner else a

        def ki(a, c):
            return a if q_inner else c

        q_spec = pl.BlockSpec((1, bq, width),
                              lambda b, h, a, c: (b, qi(a, c), h))
        kv_spec = pl.BlockSpec((1, bk, width),
                               lambda b, h, a, c: (b, ki(a, c), h))
        lse_spec = pl.BlockSpec((1, 1, width // hd, bq),
                                lambda b, h, a, c: (b, h, 0, qi(a, c)))
        acc = pltpu.VMEM((bk if q_inner else bq, width), jnp.float32)
        return pl.pallas_call(
            functools.partial(_bwd_kernel, q_inner=q_inner, hd=hd,
                              scale=scale, causal=causal),
            grid=(n, e // width) + ((nk, nq) if q_inner else (nq, nk)),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, lse_spec],
            out_specs=[q_spec if x is q else kv_spec for x in outs],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in outs],
            scratch_shapes=[acc, acc] if q_inner else [acc],
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
            interpret=_interpret(), name=name,
        )(q, k, v, o, do, lse)

    if nk == 1:
        return call("flash_mha_bwd_fused", True, (q, k, v))
    dk, dv = call("flash_mha_bwd_dkv", True, (k, v))
    dq, = call("flash_mha_bwd_dq", False, (q,))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, num_heads: int, causal: bool, scale: float):
    """``q``: (n, sq, h * hd); ``k``, ``v``: (n, sk, h * hd), heads side by
    side in the minor dim -> (n, sq, h * hd).  The caller checks
    :func:`supported`."""
    return _forward(q, k, v, num_heads, causal, scale)[0]


def _vjp_fwd(q, k, v, num_heads, causal, scale):
    o, lse = _forward(q, k, v, num_heads, causal, scale)
    return o, (q, k, v, o, lse)


flash_attention.defvjp(_vjp_fwd, _backward)
