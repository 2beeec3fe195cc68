"""The repo's own Pallas kernel for a prompt chunk's LATENT attention on a
TPU: the chunk's queries against the slot's cached latent rows, EXPANDED a
block of keys at a time, with the expanded keys and values, the scores and
the probabilities of a block never leaving VMEM.

The contract is ``LatentAttention._over_key_blocks`` under the expanded
``block`` of ``_chunk_expanded`` (``ops/latent_attention.py``): bfloat16
operands, float32 accumulation and softmax statistics, the finite
``NEG_INF`` mask on global positions, probabilities rounded to the operands'
dtype before the value product, key blocks ``0 .. last // keys`` only
(``last`` the chunk's last real position), so a chunk costs what the prompt
so far costs.  What changes is where the transients live.  XLA's loop wrote,
for every block of 512 keys, the per-head keys and values (2 x 16.8 MB at
128 heads) and a ``(heads, 512, 512)`` float32 score tensor (134 MB, read
and written four times: the maximum, the exponential, the sum, the
probabilities) to HBM: about 0.6 of the block's 0.8 ms on a v5e (PERF.md
section 6, PR 42).  Here a block's rows are copied to VMEM once a group of
heads and everything made from them stays there.

**The history** is gathered ONCE a call by the slot's page table (the
stored rows, ``(pages_per_slot x page, row_width)``: 16.4 MB at 12 800
positions, where the loop gathered a block at a time), sentinel entries
clipped as the gather's ``mode="clip"`` does; the kernel reads it from HBM a
block of ``keys`` rows at a time, one contiguous copy, double-buffered: the
copy of block ``k + 1`` (or, behind a group's last block, of block 0 for
the next group of heads) runs under the arithmetic of block ``k``.  Which
buffer a grid step starts in is carried in SMEM (the grid is sequential).

**One grid step a group of heads**, not a (head, block) pair: the key
blocks are a ``fori_loop`` INSIDE the step whose trip count is the blocks
the chunk can see, so a 512-token chunk at position 2 048 of a 12 800-row
table runs 4 blocks a head, not 25 grid steps of which 21 do nothing.  The
blocks wholly at or under the chunk's first position run without the mask
(a loop of their own); the ones that touch the diagonal compare positions.
A head's ``[W_UK | W_UV]`` (``wkv_b`` as it is stored, ``(nope + v,
kv_rank)``: 256 KB) and its queries arrive by BlockSpec once a step.

**A visit** (one block, one head).  The block's latent part ``c`` (keys,
kv_rank) expands with two products against the weights as they lie (both
contract the weights' minor dim, so nothing is transposed): ``k_nope = c
W_UK^T`` (keys, nope) and ``v^T = W_UV c^T`` (v, keys).  The scores are ONE
product over ``[k_nope | k_pe]`` (the row's rotary part with its zero
padding, whole lane tiles) against ``[q_nope | q_pe | 0]``, TRANSPOSED
(keys along the sublanes, as ``flash_kernel.py``): a query's maximum and
sum are ``(1, B)`` rows reduced by plain vector operations, and ``v^T p^T``
accumulates ``o^T`` (v, B), transposed once a head when the last block is
done.  The output block is ``(B, heads x v)`` float32, a token's heads side
by side: what ``LatentAttention._out`` folds.

The queries are padded to whole 128-lane tiles of ``B`` by the wrapper (a
2-token bucket costs what a 128-token one does: the expansion of the
history, which no bucket avoids); a padded row's result is dropped.

The kernel's ``name=`` is ``latent_chunk_attention`` in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_kernel import _NT, LANES, NEG_INF, _dot, _interpret

_HEADS = (4, 2, 1)          # heads a grid step: the most that divides
_VMEM_LIMIT = 32 << 20


def supported(backend: str, dtype, kv_rank: int, row_width: int,
              nope_dim: int, v_dim: int, page_size: int, keys: int,
              distributed: bool = False, training: bool = False) -> bool:
    """What the kernel needs (``dtype``: the pool's and the operands', an
    array's ``.dtype``): a TPU; bfloat16 (the blocks are sized for it and
    the MXU takes it in one pass); the latent part, the stored row, a
    head's ``nope`` and ``v`` whole 128-lane tiles, with a rotary part
    behind the latent one; pages that tile a block of ``keys`` cached rows
    (what a visit takes: the caller's, the loop's own); no gradient (the
    kernel is forward-only); and ONE device, as the other kernels (GSPMD
    would all-gather for an opaque custom call; nothing serves across
    chips: ROADMAP M1).  The chunk's bucket is NOT asked: the wrapper pads
    the queries to whole lane tiles."""
    if (backend != "tpu" or distributed or training
            or dtype != jnp.bfloat16):
        return False
    return (kv_rank % LANES == 0 and row_width % LANES == 0
            and row_width > kv_rank and nope_dim % LANES == 0
            and v_dim % LANES == 0 and keys % page_size == 0)


def _kernel(span_ref, q_ref, w_ref, rows_hbm, o_ref, buf, m_scr, l_scr,
            acc_scr, sem, turn, *, scale, rank, nope):
    i, steps = pl.program_id(0), pl.num_programs(0)
    heads = w_ref.shape[0]
    keys = buf.shape[1]
    queries = q_ref.shape[0]
    qw = q_ref.shape[1] // heads        # [q_nope | q_pe | 0]
    vw = o_ref.shape[1] // heads
    start, last = span_ref[0], span_ref[1]
    blocks = jnp.minimum(last // keys + 1, rows_hbm.shape[0] // keys)
    # blocks every key of which every query sees: (k + 1) keys - 1 <= start
    clear = jnp.minimum((start + 1) // keys, blocks)

    def copy(k, slot):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(k * keys, keys), keys)],
            buf.at[slot], sem.at[slot])

    @pl.when(i == 0)
    def _():
        turn[0] = 0
        copy(0, 0).start()

    slot0 = turn[0]
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def visit(k, masked):
        slot = (slot0 + k) % 2

        @pl.when(k + 1 < blocks)
        def _():
            copy(k + 1, 1 - slot).start()

        @pl.when(jnp.logical_and(k + 1 == blocks, i + 1 < steps))
        def _():    # the next heads start over at block 0
            copy(0, 1 - slot).start()

        copy(k, slot).wait()
        c = buf[slot, :, :rank]                                # (keys, rank)
        k_pe = buf[slot, :, rank:]                  # rotary part, zero-padded
        if masked:
            kpos = k * keys + jax.lax.broadcasted_iota(
                jnp.int32, (keys, queries), 0)
            qpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (keys, queries), 1)
            dead = kpos > qpos
        for g in range(heads):
            k_nope = _dot(c, w_ref[g, :nope, :], _NT).astype(c.dtype)
            v_t = _dot(w_ref[g, nope:, :], c, _NT).astype(c.dtype)
            s_t = _dot(jnp.concatenate([k_nope, k_pe], axis=1),
                       q_ref[:, g * qw:(g + 1) * qw], _NT) * scale
            if masked:
                s_t = jnp.where(dead, NEG_INF, s_t)          # (keys, B)
            m_prev = m_scr[g]                                   # (1, B)
            m_next = jnp.maximum(m_prev,
                                 jnp.max(s_t, axis=0, keepdims=True))
            p_t = jnp.exp(s_t - m_next)
            alpha = jnp.exp(m_prev - m_next)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p_t, axis=0, keepdims=True)
            m_scr[g] = m_next
            acc_scr[g] = alpha * acc_scr[g] + _dot(v_t, p_t.astype(c.dtype))

    def over(lo, hi, masked):
        def body(k, carry):
            visit(k, masked)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    over(0, clear, False)
    over(clear, blocks, True)
    turn[0] = (slot0 + blocks) % 2
    for g in range(heads):
        o_ref[:, g * vw:(g + 1) * vw] = (acc_scr[g] / l_scr[g]).T


# jitted so that the equal-shaped layers of a model share ONE traced and
# lowered kernel a bucket (flash_kernel.py: tracing it per layer cost 3.5 s
# of set-up)
@functools.partial(jax.jit, static_argnames=("scale", "rank", "keys"))
def latent_chunk_attention(q_nope, q_pe, pool, table, w_kvb, start, length,
                           *, scale: float, rank: int, keys: int):
    """``q_nope`` (B, H, nope), ``q_pe`` (B, H, rope): the chunk's queries,
    rotated, positions ``start .. start + B - 1`` of which the first
    ``length`` are real; ``pool`` (num_pages, page, row_width), the chunk's
    rows already written; ``table`` (pages_per_slot,) int32, the slot's
    pages (the sentinel ``num_pages`` where none); ``w_kvb`` (H, nope + v,
    rank), a head's ``[W_UK | W_UV]`` -> (1, B, H, v) f32, each head's
    causal attention over the EXPANDED history (the module's docstring).
    ``keys``: cached rows a visit, rounded down to whole pages (the loop's
    ``_KEY_BLOCK``, so both cores cut the history alike).  The caller
    checks :func:`supported`."""
    B, H, nope = q_nope.shape
    page, width = pool.shape[1], pool.shape[2]
    vw = w_kvb.shape[1] - nope
    block_pages = max(1, keys // page)
    keys = block_pages * page
    heads = next(g for g in _HEADS if H % g == 0)
    # the whole table's rows, whole key blocks of them
    table = jnp.pad(table, (0, -table.shape[0] % block_pages),
                    constant_values=pool.shape[0])
    rows = jnp.take(pool, table, axis=0, mode="clip").reshape(-1, width)
    # [q_nope | q_pe | 0]: what contracts with [k_nope | stored rotary part]
    padded = -(-B // LANES) * LANES
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    q = jnp.pad(q, ((0, padded - B), (0, 0),
                    (0, nope + width - rank - q.shape[-1])))
    qw = q.shape[-1]
    span = jnp.stack([start, start + jnp.maximum(length, 1) - 1]).astype(
        jnp.int32)
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, nope=nope),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H // heads,),
            in_specs=[
                pl.BlockSpec((padded, heads * qw), lambda i, *_: (0, i)),
                pl.BlockSpec((heads, nope + vw, rank),
                             lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((padded, heads * vw),
                                   lambda i, *_: (0, i)),
            scratch_shapes=[
                pltpu.VMEM((2, keys, width), rows.dtype),
                pltpu.VMEM((heads, 1, padded), jnp.float32),
                pltpu.VMEM((heads, 1, padded), jnp.float32),
                pltpu.VMEM((heads, vw, padded), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((padded, H * vw), jnp.float32),
        compiler_params=params, interpret=_interpret(),
        name="latent_chunk_attention",
    )(span, q.reshape(padded, H * qw), w_kvb, rows)
    return out[:B].reshape(1, B, H, vw)
