"""The repo's own Pallas kernel for the CHOICE of a decoded token under a
learned selection of keys (``MultiHeadAttention(sparse=)``): one query a
slot scores every live position of its slot against the paged pool of the
indexer's keys, read where it lies, and finds the threshold of its ``topk``
best while the slot's score row is in VMEM.

What it replaces (``MultiHeadAttention._sparse_rows``' first half as XLA ran
it): ``jnp.take(i_pool, table)`` wrote every slot's WHOLE page table of ``ik``
out as a view (24 x 25 088 x 128 lanes x 2 B = 154 MB a layer in the keye
cell, for slots of which half decode at a third of that length), the scores
were made from the view, and ``jax.lax.top_k`` sorted every row of them in
memory.  A slot's score row is 100 KB: here it never leaves VMEM before the
threshold is known.

**The scheme is ``paged_decode_kernel``'s.**  One sequential grid step a
slot; a slot whose write page is the pool's ``no_page`` sentinel is not
decoding and costs an empty step; the live pages ``table[i, 0 .. pos[i] //
page]`` of ``ik`` are copied into VMEM in double-buffered GROUPS of up to
``_GROUP_ROWS`` rows, one asynchronous copy a page, the next group — this
slot's, or the first of the next slot that decodes — in flight under this
one's arithmetic; which buffer a slot starts in is carried in SMEM.  Pages
past ``pos`` are never read, so a stale table entry there costs and leaks
nothing; rows of a buffer no copy wrote are masked by position.

**Scores** are ``ops/attention.index_scores``' arithmetic (the same bits
under the CPU's interpreter; on the chip the same precision, the products
summed in the kernel's order and not XLA's over a view), a chunk of
``_CHUNK`` keys at a time: per index head the product ``qi[h] .
ki`` in the pool's dtype with float32 accumulation (the heads are the rows
of ONE ``(heads, width) x (width, chunk)`` product; the stored row's zero
padding contracts with the query's), ``relu``, times ``wi[h]`` and summed
over the heads in float32 on the vector unit, ``+ 0.0``, ``NEG_INF`` past
``pos``.  The row is kept DENSE: position ``p`` is lane ``p % 128`` of row
``p // 128`` of a ``(rows, 128)`` float32 block (25 vector registers at
25 088 positions, not the 196 a ``(1, L)`` row would take), which is the
kernel's first output; rows past the last live chunk are ``NEG_INF`` without
being read.

**The threshold** is ``ops/attention.select_threshold``'s search on that
block: the order-preserving integer image of the scores, 32 passes that each
count the positions at or over a candidate, and only where more positions
tie AT the threshold than are still needed the second search, over
positions, for the last tied one chosen.  Exact, of equal scores the lower
position first: with :func:`ops.attention.selected` the set ``jax.lax.top_k``
names on the kernel's scores.  (The image is compared as SIGNED integers, the unsigned image with
its top bit flipped: the same order, and a comparison every vector unit has.)

**The row list** that ``_sparse_rows``' gather wants is made from the chosen
set by RANK, without a sort and without a scatter (:func:`rows_by_rank`):
prefix counts and one-hot products, exact in bfloat16 because every factor
is an integer under 256.

The kernel's ``name=`` is ``paged_index_select`` in a device trace (not
``paged_decode..``, ``flash..`` or ``ragged-dot..``: readers of the benchmark
find other kernels by those prefixes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import dtype_itemsize
from .flash_kernel import _NT, LANES, NEG_INF, _dot, _interpret
from .paged_decode_kernel import _HEAD_ROWS, _VMEM_LIMIT

_CHUNK = 512                # keys of one product
_GROUP_ROWS = 2048          # key rows of one copy group, at most
_TOP = -(1 << 31)           # the image's top bit, as an int32
_NEG_INF_BITS = int(np.float32(NEG_INF).view(np.int32))


def _geometry(page: int, pages_per_slot: int):
    """``(chunk, group, rows)``: keys of one product (whole pages, whole
    128-position rows), key rows of one copy group (whole chunks, no more
    than a slot holds) and rows of the score block (every chunk of the
    table, whole sublane tiles)."""
    chunk = max(page, _CHUNK)
    whole = -(-pages_per_slot * page // chunk) * chunk
    group = min(whole, max(chunk, _GROUP_ROWS // chunk * chunk))
    return chunk, group, -(-whole // LANES // 8) * 8


def _kernel(table_ref, pos_ref, wp_ref, q_ref, w_ref, ik_hbm, s_ref, thr_ref,
            last_ref, buf, sems, turn, *, page, pages_per_slot, chunk, topk):
    i, slots = pl.program_id(0), pl.num_programs(0)
    no_page = ik_hbm.shape[0]
    group = buf.shape[1]
    group_pages = group // page
    positions = pages_per_slot * page
    rows = s_ref.shape[1]

    def decodes(s):
        return wp_ref[s] != no_page

    def live_pages(s):
        return jnp.minimum(pos_ref[s] // page + 1, pages_per_slot)

    def next_decoding(s):
        """The first slot at or after ``s`` that decodes; ``slots`` if
        none does."""
        return jax.lax.while_loop(
            lambda j: jnp.logical_and(
                j < slots,
                jnp.logical_not(decodes(jnp.minimum(j, slots - 1)))),
            lambda j: j + 1, s)

    def copy(pid, b, p):
        dst = pl.ds(pl.multiple_of(p * page, page), page)
        return pltpu.make_async_copy(ik_hbm.at[pid], buf.at[b, dst],
                                     sems.at[b])

    def each_page(s, g, b, do):
        """``do`` on the copy of every live page of slot ``s``'s group
        ``g``, into buffer ``b``."""
        first = g * group_pages
        entry = s * pages_per_slot + first
        count = jnp.minimum(live_pages(s) - first, group_pages)

        def body(p, carry):
            # clipped like the gather's mode="clip": a live page is never
            # the sentinel, and a copy must not leave the pool whatever
            do(copy(jnp.minimum(table_ref[entry + p], no_page - 1), b, p))
            return carry

        jax.lax.fori_loop(0, count, body, 0)

    def start(s, g, b):
        each_page(s, g, b, lambda c: c.start())

    def wait(s, g, b):
        each_page(s, g, b, lambda c: c.wait())

    @pl.when(i == 0)
    def _():
        turn[0] = 0
        first = next_decoding(0)

        @pl.when(first < slots)
        def _():
            start(first, 0, 0)

    s_ref[...] = jnp.full(s_ref.shape, NEG_INF, jnp.float32)

    @pl.when(jnp.logical_not(decodes(i)))
    def _():
        # every score equal: the first ``topk`` positions, all of them dead
        thr_ref[i] = jnp.int32(_NEG_INF_BITS)
        last_ref[i] = jnp.int32(topk - 1)

    @pl.when(decodes(i))
    def _():
        pos = pos_ref[i]
        groups = pl.cdiv(live_pages(i), group_pages)
        b0 = turn[0]
        after = next_decoding(i + 1)
        q, w = q_ref[0], w_ref[0]               # (heads, width), (heads, 1)

        def one_group(g, carry):
            b = (b0 + g) % 2

            @pl.when(g + 1 < groups)
            def _():
                start(i, g + 1, 1 - b)

            @pl.when(jnp.logical_and(g + 1 == groups, after < slots))
            def _():
                start(after, 0, 1 - b)

            wait(i, g, b)
            base = g * group
            chunks = pl.cdiv(jnp.minimum(pos + 1 - base, group), chunk)

            def one_chunk(c, carry):
                at = pl.multiple_of(c * chunk, chunk)
                s = _dot(q, buf[b, pl.ds(at, chunk), :], _NT)  # (heads, chunk)
                # (+ 0.0: one zero only, as index_scores)
                row = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0,
                              keepdims=True) + 0.0
                kpos = base + at + jax.lax.broadcasted_iota(
                    jnp.int32, (1, chunk), 1)
                row = jnp.where(kpos > pos, NEG_INF, row)
                r0 = (base + at) // LANES
                for j in range(chunk // LANES):
                    s_ref[0, pl.ds(r0 + j, 1), :] = row[:, j * LANES:
                                                        (j + 1) * LANES]
                return carry

            return jax.lax.fori_loop(0, chunks, one_chunk, carry)

        jax.lax.fori_loop(0, groups, one_group, 0)
        turn[0] = (b0 + groups) % 2

        # ---- select_threshold, on the block -----------------------------
        bits = jax.lax.bitcast_convert_type(s_ref[0], jnp.int32)
        top = jnp.int32(_TOP)
        # the order-preserving image, top bit flipped: signed comparisons
        key = jnp.where(bits < 0, ~bits ^ top, bits)

        def count(mask):
            return jnp.sum(mask.astype(jnp.int32))

        def bit(n, ans):    # ``ans``: the unsigned image, held in an int32
            cand = ans | jax.lax.shift_right_logical(top, n)
            return jnp.where(count(key >= (cand ^ top)) >= topk, cand, ans)

        ans = jax.lax.fori_loop(0, 32, bit, jnp.int32(0))
        # the threshold's bits (a scalar is not bitcast here: the wrapper's)
        thr_ref[i] = jnp.where(ans < 0, ans ^ top, ~ans)
        at_thr = ans ^ top
        tied = key == at_thr
        need = topk - count(key > at_thr)                  # >= 1 of the tied
        width = positions.bit_length()

        def where_need_is_met():
            # the largest P with fewer than ``need`` tied positions under it
            kpos = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
                    * LANES
                    + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))

            def step(n, p):
                cand = p | jax.lax.shift_left(jnp.int32(1), width - 1 - n)
                return jnp.where(count(tied & (kpos < cand)) < need, cand, p)

            return jax.lax.fori_loop(0, width, step, jnp.int32(0))

        last_ref[i] = jax.lax.cond(count(tied) == need,
                                   lambda: jnp.int32(positions),
                                   where_need_is_met)


# jitted so that the equal-shaped layers of a model share ONE traced and
# lowered kernel (flash_kernel.py: tracing it per layer cost 3.5 s of set-up)
@functools.partial(jax.jit, static_argnums=(6,))
def paged_index_select(qi, wi, i_pool, table, pos, write_pages, topk: int):
    """``qi`` (slots, heads, di): each slot's current-token index queries;
    ``wi`` (slots, heads) f32: the heads' weights; ``i_pool`` (num_pages,
    page, width): the indexer's key of every cached position, the new rows
    already written, ``width >= di`` with the padding zero; ``table``
    (slots, pages_per_slot) int32; ``pos`` (slots,) int32 position of the
    current token; ``write_pages`` (slots,) int32, ``num_pages`` where a
    slot is not decoding; ``topk < pages_per_slot * page`` ->

    * ``scores`` (slots, L) f32, ``L = pages_per_slot * page``:
      :func:`ops.attention.index_scores` of the slot's query against its
      table's rows, ``NEG_INF`` past ``pos``;
    * ``thr`` (slots,) f32, ``last`` (slots,) int32:
      :func:`ops.attention.select_threshold` of each row.

    A slot that is not decoding reads no page: its scores are all
    ``NEG_INF`` and its ``thr`` / ``last`` name the first ``topk``
    positions.  Takes the ``ik`` leaf of every pool that
    ``paged_decode_kernel.supported`` takes (one device, bfloat16 or
    float32): a stored row of whole 128-lane tiles, a page of whole sublane
    tiles (a copy lands on tile boundaries) that tiles a 128-position row
    of the score block or is tiled by it."""
    slots, heads, di = qi.shape
    page, width = i_pool.shape[1], i_pool.shape[2]
    sublanes = 8 * (4 // dtype_itemsize(i_pool.dtype))
    assert width % LANES == 0 and (_interpret() or (
        page % sublanes == 0
        and (LANES % page == 0 or page % LANES == 0))), i_pool.shape
    pages_per_slot = table.shape[1]
    positions = pages_per_slot * page
    chunk, group, rows = _geometry(page, pages_per_slot)
    hp = -(-heads // _HEAD_ROWS) * _HEAD_ROWS
    q = jnp.pad(qi.astype(i_pool.dtype),
                ((0, 0), (0, hp - heads), (0, width - di)))
    w = jnp.pad(wi.astype(jnp.float32), ((0, 0), (0, hp - heads)))[..., None]
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    scores, thr, last = pl.pallas_call(
        functools.partial(_kernel, page=page, pages_per_slot=pages_per_slot,
                          chunk=chunk, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(slots,),
            in_specs=[pl.BlockSpec((1, hp, width), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec((1, hp, 1), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, rows, LANES),
                                    lambda i, *_: (i, 0, 0)),
                       scalars, scalars],
            scratch_shapes=[pltpu.VMEM((2, group, width), i_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((slots, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((slots,), jnp.int32),
                   jax.ShapeDtypeStruct((slots,), jnp.int32)],
        compiler_params=params, interpret=_interpret(),
        name="paged_index_select",
    )(table.reshape(-1), pos, write_pages, q, w, i_pool)
    return (scores.reshape(slots, rows * LANES)[:, :positions],
            jax.lax.bitcast_convert_type(thr, jnp.float32), last)


def rows_by_rank(keep, topk: int):
    """The positions a mask names, as a list: ``keep`` (n, L) bool with
    EXACTLY ``topk`` positions set a row -> ``idx`` (n, topk) int32,
    ascending.

    By RANK, without a sort (``jax.lax.top_k`` is one) and without a scatter
    (``jnp.nonzero(size=)`` is one): a position's rank is the count of
    chosen positions under it, an exclusive prefix count inside its block of
    128 positions (a product with a triangular 0/1 matrix) plus its block's
    offset (the same over the block counts); slot ``j`` of the list then
    finds ITS block by a one-hot over the blocks, that block's in-block
    ranks by a product with the one-hot, and its lane as the one whose rank
    is ``j`` less the block's offset: ``position = 128 x block + lane``.
    Every factor of every product is an integer under 256, so bfloat16
    operands with float32 accumulation are exact."""
    n, L = keep.shape
    blocks = -(-L // LANES)
    keep = jnp.pad(keep, ((0, 0), (0, blocks * LANES - L))).reshape(
        n, blocks, LANES)
    lane = jnp.arange(LANES)
    under = (lane[:, None] < lane[None, :]).astype(jnp.bfloat16)
    kept = keep.astype(jnp.bfloat16)
    # chosen lanes under each lane of its block; -1 where not chosen
    inner = jnp.where(keep, jnp.einsum(
        "nbl,lm->nbm", kept, under, preferred_element_type=jnp.float32), -1.0)
    count = jnp.sum(keep, axis=-1, dtype=jnp.int32)          # (n, blocks)
    # blocks under each block: counts under 256 in bf16, sums in f32
    block = jnp.arange(blocks)
    offset = jnp.einsum(
        "nb,bc->nc", count.astype(jnp.bfloat16),
        (block[:, None] < block[None, :]).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    j = jnp.arange(topk)[None, :, None]
    mine = (offset[:, None, :] <= j) & (j < (offset + count)[:, None, :])
    ranks = jnp.einsum("njb,nbl->njl", mine.astype(jnp.bfloat16),
                       inner.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    start = jnp.sum(jnp.where(mine, offset[:, None, :], 0), axis=-1)
    which = jnp.sum(jnp.where(mine, block, 0), axis=-1)
    here = ranks == (jnp.arange(topk)[None, :] - start
                     ).astype(jnp.float32)[..., None]
    at = jnp.sum(jnp.where(here, lane, 0), axis=-1)          # (n, topk)
    return (which * LANES + at).astype(jnp.int32)
