"""The repo's own Pallas decode attention for TPU: one query position a
slot against the PAGED KV pools, read where they lie.

The pools are stored lane-dense, ``(num_pages, page, heads * head_dim)``
(``MultiHeadAttention.serve_state``).  The gathered decode
(``ops/attention._decode_attention`` behind ``_gather_pages``) writes
every slot's WHOLE page table out as a view, unfolds the view to
``(.., heads, head_dim)`` and reads that twice: five passes over
``slots x max_seq`` positions a layer, whatever each slot's length is.
This kernel leaves the pools in HBM and copies, for slot ``i``, only pages
``table[i, 0 .. pos[i] // page]`` into VMEM, once; a slot whose write page
is the pool's ``no_page`` sentinel is not decoding (that is how the engine
says so), costs one empty grid step and reads zero.

**One grid step a slot, not a page.**  A step's pages arrive in copy
GROUPS of up to ``_GROUP_ROWS`` key rows, one asynchronous copy a page
and pool, double-buffered: before a group is computed on, the next one —
this slot's, or the first of the next slot that decodes — is put in
flight, so the copies of slot ``i + 1`` run under the arithmetic of slot
``i``.  Which buffer a slot starts in is carried from step to step in
SMEM (the grid is sequential).

**The heads are never unfolded.**  The query row ``(1, h * hd)`` becomes a
block-diagonal ``(R, h * hd)`` matrix (row ``r`` keeps head ``r``'s lanes,
``R`` the heads rounded up to 16 sublanes), so ``Q K^T`` over a chunk of
``(T, h * hd)`` key rows is ``(R, T)`` scores, one row a head; the online
softmax runs on those in f32 with the same finite ``NEG_INF`` mask on
``kpos > pos`` as everywhere; ``P V`` is ``(R, h * hd)``, of which row
``r`` is right in head ``r``'s lanes, and the diagonal blocks are taken
once a slot.  The ``h``-fold extra multiply-adds are noise: a decode step
is bound by the bytes of the pages.

Rows of a buffer that no copy of this group wrote (the tail past the
slot's last live page) hold what an earlier group left there, or the
zeros the first step stores: finite, and masked to an exact 0.0 weight.
Pages beyond ``pos`` are never read, so a stale table entry there — a
page another stream owns by now — costs nothing and leaks nothing.

**Grouped queries.**  With fewer key/value heads than query heads and a
head that is whole lane tiles (``head_dim % 128 == 0``), the pools are
``(num_pages, page, kv_heads * head_dim)`` and key/value head ``j`` is
lanes ``j * head_dim ..`` of a row: its GROUP of query heads arrives as
``_HEAD_ROWS`` rows (the group, zero-padded) of a ``(kv_heads * 16,
head_dim)`` block, and the products are ``(16, head_dim) x (head_dim, T)``
and ``(16, T) x (T, head_dim)`` a key/value head, on lane-aligned slices
of the same buffers; nothing is block-diagonal and nothing is multiplied
that is not needed.

**A window.**  With ``window > 0`` a slot reads positions ``pos - window +
1 .. pos`` only: its first copied page is the one that holds the lower
bound, the positions below it in that page are masked like the ones above
``pos``, and the page table is a RING (logical page ``p`` is entry ``p %
pages_per_slot``: ``MultiHeadAttention._serve_step_window``).  One copy
group then holds a whole window.

**A latent row.**  With ``value_lanes > 0`` there is ONE pool: a row is
what every query head shares (``LatentAttention``'s compressed key/value
row, its rotary part beside it, zero-padded to whole lane tiles: 576 values
stored as 640), scores contract the whole row and the VALUES are the first
``value_lanes`` lanes of the same rows, so a page is copied once for both
products.  It is the grouped form with one key/value head whose group is
all the query heads (rounded up to sublane tiles), one product a chunk.

**A chosen set.**  With ``keep`` given (``masked``: an operand STATIC in its
presence; absent, the kernel traces to what it always traced to) a slot
attends over a SET of its positions (``MultiHeadAttention(sparse=)``'s
``topk`` best by the indexer): ``keep`` is a per-slot ``(1, positions / 128,
128)`` int32 block by an ordinary ``BlockSpec``, position ``p`` lane ``p %
128`` of row ``p // 128`` (the layout ``paged_index_select`` writes its
scores in), non-zero where chosen.  Every live page is still copied, once:
a page is what this chip copies cheaply (a 1 KB row costs an asynchronous
copy as much as a 16 KB page does, and XLA's gather of the chosen rows
more than the pages' copies), and the set needs no list.  A position is
dead where it is past ``pos`` OR not kept.  A chunk, the first one too, may
keep nothing, and ``exp(NEG_INF - NEG_INF)`` is 1: the dead entries of the
probabilities are zeroed explicitly, as ``_sparse_over_blocks`` does.  The
caller keeps at least one live position of every decoding slot (a query's
``topk`` is never empty), so the sum is never 0.

The kernel's ``name=`` is ``paged_decode_attention`` in the device trace
(not ``flash_..``: ``perfbench/flops``' ``FLASH_KERNELS`` matches on that
prefix and the train cells' ``flash_share`` must not learn of it).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import dtype_itemsize
from .flash_kernel import _NT, LANES, NEG_INF, _dot, _interpret

_HEAD_ROWS = 16             # a bf16 sublane tile: heads round up to it
_GROUP_ROWS = 512           # key rows of one copy group, at most
_BUFFER_BYTES = 8 << 20     # the four group buffers (K, V; two each)
_VMEM_LIMIT = 32 << 20


_LATENT_ROWS = 256          # query heads over one latent row, at most
_LATENT_CHUNK = 512         # key rows of one product over latent rows
_SPARSE_CHUNK = 1024        # key rows of one product under a chosen set
_SPARSE_GROUP_ROWS = 2048   # and of one copy group there, at most


def supported(backend: str, dtype, num_heads: int, head_dim: int,
              page_size: int, distributed: bool,
              num_kv_heads: int = 0, value_lanes: int = 0) -> bool:
    """What the in-place read needs (``dtype``: the pool's, an array's
    ``.dtype``): a TPU; a pool dtype the MXU takes;
    the folded row a whole number of 128-lane tiles that no head straddles
    unevenly; a page that is whole sublane tiles of the dtype (a copy
    lands on tile boundaries) and tiles a 128-row chunk or is tiled by
    it; and ONE device — GSPMD would all-gather a sharded pool for an
    opaque custom call (no cell serves across chips yet: ROADMAP W6).
    Fewer key/value heads than query heads (``num_kv_heads``): a head of
    whole lane tiles and a group of at most ``_HEAD_ROWS`` query heads.
    A latent row (``value_lanes > 0``, ``head_dim`` the STORED row's width,
    ``num_kv_heads`` 1): taken where the row and its value part are whole
    lane tiles and the heads are at most ``_LATENT_ROWS``.  A bare 576-wide
    row (512 compressed values and 64 rotary ones) is NOT taken: it is four
    and a half lane tiles, so a page's copy would end mid-tile and the
    scores' contraction would read the half tile beside it;
    ``LatentAttention`` therefore stores the row padded with zeros to 640,
    which is what the TPU's tiled memory holds for a 576-wide array anyway,
    and that row is taken."""
    if backend != "tpu" or distributed or dtype not in (jnp.bfloat16,
                                                        jnp.float32):
        return False
    sublanes = 8 * (4 // dtype_itemsize(dtype))
    if value_lanes:
        return (head_dim % LANES == 0 and value_lanes % LANES == 0
                and value_lanes <= head_dim and num_heads <= _LATENT_ROWS
                and page_size % sublanes == 0
                and (LANES % page_size == 0 or page_size % LANES == 0))
    kv_heads = num_kv_heads or num_heads
    if kv_heads != num_heads and (head_dim % LANES
                                  or num_heads // kv_heads > _HEAD_ROWS):
        return False
    return ((kv_heads * head_dim) % LANES == 0
            and (LANES % head_dim == 0 or head_dim % LANES == 0)
            and page_size % sublanes == 0
            and (LANES % page_size == 0 or page_size % LANES == 0))


def _geometry(page: int, pages_per_slot: int, e: int, itemsize: int,
              window: int = 0, group_rows: int = _GROUP_ROWS):
    """``(chunk, group)``: key rows of one product (whole pages, 128 or
    one larger page) and of one copy group (whole chunks: no more than a
    slot can hold, than ``group_rows`` or one window and the page it
    straddles, than the buffers' budget)."""
    chunk = max(page, LANES)
    whole = -(-pages_per_slot * page // chunk) * chunk
    fits = _BUFFER_BYTES // (4 * e * itemsize) // chunk * chunk
    want = group_rows if not window else max(group_rows, window + page)
    return chunk, max(chunk, min(whole, -(-want // chunk) * chunk, fits))


def _kernel(table_ref, pos_ref, wp_ref, q_ref, *refs, num_heads, kv_heads,
            scale, page, pages_per_slot, chunk, window, value_lanes=0,
            masked=False):
    keep_ref = None
    if masked:          # the chosen set, a (1, positions / 128, 128) block
        keep_ref, *refs = refs
    if value_lanes:     # one pool: the values are lanes of the key rows
        k_hbm, o_ref, k_buf, sems, turn = refs
        v_hbm = v_buf = None
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, turn = refs
    i, slots = pl.program_id(0), pl.num_programs(0)
    no_page = k_hbm.shape[0]
    group = k_buf.shape[1]
    group_pages = group // page
    e = k_buf.shape[2]
    grouped = kv_heads != num_heads
    head_dim = e // kv_heads
    rows = -(-num_heads // _HEAD_ROWS) * _HEAD_ROWS
    # query rows a key/value head: a sublane tile, or all of a latent row's
    head_rows = rows if value_lanes else _HEAD_ROWS

    def decodes(s):
        return wp_ref[s] != no_page

    def first_page(s):
        """The logical page a slot's copies start at: 0, or with a window
        the page of position ``pos - window + 1``."""
        if not window:
            return 0
        return jnp.maximum(pos_ref[s] - (window - 1), 0) // page

    def live_pages(s):
        """Logical pages ``first_page(s) ..`` that hold live positions."""
        if not window:
            return jnp.minimum(pos_ref[s] // page + 1, pages_per_slot)
        return pos_ref[s] // page + 1 - first_page(s)

    def next_decoding(s):
        """The first slot at or after ``s`` that decodes; ``slots`` if
        none does."""
        return jax.lax.while_loop(
            lambda j: jnp.logical_and(
                j < slots,
                jnp.logical_not(decodes(jnp.minimum(j, slots - 1)))),
            lambda j: j + 1, s)

    def each_page(s, g, buf, do):
        """``do`` on the two copies (K, V) of every live page of slot
        ``s``'s group ``g``, into buffer ``buf``."""
        first = g * group_pages
        count = jnp.minimum(live_pages(s) - first, group_pages)
        base = first_page(s)

        def body(p, carry):
            # clipped like the gather's mode="clip": a live page is never
            # the sentinel, and a copy must not leave the pool whatever
            entry = first + p
            if window:      # the table is a ring of pages_per_slot pages
                entry = (base + entry) % pages_per_slot
            pid = jnp.minimum(table_ref[s * pages_per_slot + entry],
                              no_page - 1)
            dst = pl.ds(pl.multiple_of(p * page, page), page)
            do(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[buf, dst],
                                     sems.at[0, buf]))
            if v_hbm is not None:
                do(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[buf, dst],
                                         sems.at[1, buf]))
            return carry

        jax.lax.fori_loop(0, count, body, 0)

    def start(s, g, buf):
        each_page(s, g, buf, lambda copy: copy.start())

    def wait(s, g, buf):
        each_page(s, g, buf, lambda copy: copy.wait())

    @pl.when(i == 0)
    def _():
        # finite tails for the first groups (see the module's docstring)
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        if v_buf is not None:
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        turn[0] = 0
        first = next_decoding(0)

        @pl.when(first < slots)
        def _():
            start(first, 0, 0)

    @pl.when(jnp.logical_not(decodes(i)))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(decodes(i))
    def _():
        pos = pos_ref[i]
        groups = pl.cdiv(live_pages(i), group_pages)
        buf0 = turn[0]
        after = next_decoding(i + 1)
        # the position the first row of group 0 holds
        origin = first_page(i) * page
        if grouped:
            heads = tuple(range(kv_heads))
            q_heads = None
        else:
            head_of_lane = jax.lax.broadcasted_iota(
                jnp.int32, (rows, e), 1) // head_dim
            diagonal = head_of_lane == jax.lax.broadcasted_iota(
                jnp.int32, (rows, e), 0)
            # selected in f32: an i32-derived mask does not lay out as bf16's
            q_heads = jnp.where(diagonal, q_ref[0].astype(jnp.float32),
                                0.0).astype(q_ref.dtype)

        def softmax_step(carry, q, k, v, kpos, dropped=None):
            m, l, acc = carry
            s = _dot(q, k, _NT) * scale                        # (rows, T)
            dead = kpos > pos
            if window:
                dead = jnp.logical_or(dead, kpos <= pos - window)
            if dropped is not None:
                dead = jnp.logical_or(dead, dropped)
            s = jnp.where(dead, NEG_INF, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            if dropped is not None:     # a chunk may keep nothing, and
                p = jnp.where(dead, 0.0, p)     # exp(NEG_INF - NEG_INF) is 1
            alpha = jnp.exp(m - m_new)
            return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + _dot(p.astype(v.dtype), v))

        def one_group(g, carry):
            buf = (buf0 + g) % 2

            @pl.when(g + 1 < groups)
            def _():
                start(i, g + 1, 1 - buf)

            @pl.when(jnp.logical_and(g + 1 == groups, after < slots))
            def _():
                start(after, 0, 1 - buf)

            wait(i, g, buf)
            base = origin + g * group
            chunks = pl.cdiv(jnp.minimum(pos + 1 - base, group), chunk)

            def one_chunk(c, carry):
                at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                q_rows = head_rows if grouped else rows
                kpos = base + c * chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (q_rows, chunk), 1)
                dropped = None
                if masked:  # the chunk's rows of the block, side by side
                    r0 = (base + c * chunk) // LANES
                    dropped = jnp.broadcast_to(jnp.concatenate(
                        [keep_ref[0, pl.ds(r0 + j, 1), :]
                         for j in range(chunk // LANES)], axis=1),
                        (q_rows, chunk)) == 0
                if not grouped:
                    return softmax_step(carry, q_heads, k_buf[buf, at, :],
                                        v_buf[buf, at, :], kpos, dropped)
                out = []
                for j in heads:     # a key/value head: lanes j * head_dim ..
                    lanes = pl.ds(j * head_dim, head_dim)
                    qj = q_ref[0, j * head_rows:(j + 1) * head_rows, :]
                    kj = k_buf[buf, at, lanes]
                    vj = (kj[:, :value_lanes] if value_lanes
                          else v_buf[buf, at, lanes])
                    out.append(softmax_step(carry[j], qj, kj, vj, kpos,
                                            dropped))
                return tuple(out)

            return jax.lax.fori_loop(0, chunks, one_chunk, carry)

        def empty(r, width):
            return (jnp.full((r, 1), NEG_INF, jnp.float32),
                    jnp.zeros((r, 1), jnp.float32),
                    jnp.zeros((r, width), jnp.float32))

        init = (tuple(empty(head_rows, value_lanes or head_dim)
                      for _ in heads) if grouped else empty(rows, e))
        done = jax.lax.fori_loop(0, groups, one_group, init)
        turn[0] = (buf0 + groups) % 2
        if grouped:
            for j, (_, l, acc) in enumerate(done):
                o_ref[0, j * head_rows:(j + 1) * head_rows, :] = acc / l
        else:
            _, l, acc = done
            o_ref[0] = jnp.sum(jnp.where(diagonal, acc / l, 0.0), axis=0,
                               keepdims=True)


def _paged_call(q, pools, table, pos, write_pages, static, group: int,
                out_width: int, name: str, keep=None):
    """The one ``pallas_call``: ``q`` (slots, query rows, width) against
    ``pools`` (K and V, or the one latent pool), the scalars prefetched,
    the pools left in HBM, two ``group``-row buffers a pool; ``keep``
    (slots, rows, 128) int32, a slot's block in VMEM, where the step
    attends over a chosen set; -> (slots, query rows, ``out_width``) f32."""
    slots, q_rows, width = q.shape
    page, e = pools[0].shape[1], pools[0].shape[2]
    row = pl.BlockSpec((1, q_rows, width), lambda i, *_: (i, 0, 0))
    blocks, masks = [row], ()
    if keep is not None:
        static = dict(static, masked=True)
        blocks.append(pl.BlockSpec((1,) + keep.shape[1:],
                                   lambda i, *_: (i, 0, 0)))
        masks = (keep,)
    out_row = pl.BlockSpec((1, q_rows, out_width), lambda i, *_: (i, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)
    return pl.pallas_call(
        functools.partial(_kernel, page=page,
                          pages_per_slot=table.shape[1], **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(slots,),
            in_specs=blocks + [pool] * len(pools), out_specs=out_row,
            scratch_shapes=[pltpu.VMEM((2, group, e), p.dtype)
                            for p in pools]
            + [pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, q_rows, out_width),
                                       jnp.float32),
        compiler_params=params, interpret=_interpret(), name=name,
    )(table.reshape(-1), pos, write_pages, q, *masks, *pools)


@functools.partial(jax.jit, static_argnums=(5, 6))
def paged_latent_attention(q, pool, table, pos, write_pages, scale: float,
                           value_lanes: int):
    """The token step over LATENT pages: ``q`` (slots, heads, e), every
    head's query against the one row a position stores (``e`` lanes, the
    padding zero on both sides); ``pool`` (num_pages, page, e), the new
    rows already written; ``table`` / ``pos`` / ``write_pages`` as
    :func:`paged_decode_attention` takes them -> (slots, heads,
    ``value_lanes``) f32: each head's softmax-weighted sum of the rows'
    first ``value_lanes`` lanes, zero for a slot that is not decoding.
    The caller checks :func:`supported` (``value_lanes`` given)."""
    slots, heads, e = q.shape
    page, pages_per_slot = pool.shape[1], table.shape[1]
    _, group = _geometry(page, pages_per_slot, e, pool.dtype.itemsize)
    rows = -(-heads // _HEAD_ROWS) * _HEAD_ROWS
    q = jnp.pad(q, ((0, 0), (0, rows - heads), (0, 0)))
    out = _paged_call(q, (pool,), table, pos, write_pages,
                      dict(num_heads=heads, kv_heads=1, scale=scale,
                           chunk=min(group, _LATENT_CHUNK), window=0,
                           value_lanes=value_lanes),
                      group, value_lanes, "paged_latent_attention")
    return out[:, :heads]


def _over_heads(q, k_pool, v_pool, table, pos, write_pages, num_heads: int,
                scale: float, num_kv_heads: int, window: int, name: str,
                keep=None):
    """:func:`paged_decode_attention` and :func:`paged_sparse_attention`:
    the folded queries laid out as the kernel wants them (one row, or a
    key/value head's group as ``_HEAD_ROWS`` rows), the call, the output
    folded back.  ``keep`` (slots, positions) bool: the chosen set."""
    slots = q.shape[0]
    kv_heads = num_kv_heads or num_heads
    page, pages_per_slot, e = k_pool.shape[1], table.shape[1], k_pool.shape[2]
    chunk, group = _geometry(
        page, pages_per_slot, e, k_pool.dtype.itemsize, window,
        _GROUP_ROWS if keep is None else _SPARSE_GROUP_ROWS)
    if keep is not None:
        # longer groups and products than the dense read's 512 and 128
        # rows (timed on the chip: PERF.md section 6): the set costs a
        # chunk's compares whatever it keeps, and the loops' turns cost more
        # than the rows a slot's last group reads in vain; a chunk is whole
        # rows of the block and divides the group
        chunk = math.gcd(group, max(chunk, _SPARSE_CHUNK))
        positions = -(-pages_per_slot * page // chunk) * chunk
        keep = jnp.pad(keep.astype(jnp.int32),
                       ((0, 0), (0, positions - keep.shape[1]))
                       ).reshape(slots, positions // LANES, LANES)
    if kv_heads == num_heads:
        q_rows, width = 1, e
        q = q[:, None, :]
    else:       # a key/value head's group of queries as _HEAD_ROWS rows
        per, width = num_heads // kv_heads, e // kv_heads
        q_rows = kv_heads * _HEAD_ROWS
        q = jnp.pad(q.reshape(slots, kv_heads, per, width),
                    ((0, 0), (0, 0), (0, _HEAD_ROWS - per), (0, 0))
                    ).reshape(slots, q_rows, width)
    out = _paged_call(q, (k_pool, v_pool), table, pos, write_pages,
                      dict(num_heads=num_heads, kv_heads=kv_heads,
                           scale=scale, chunk=chunk, window=window),
                      group, width, name, keep)
    if kv_heads == num_heads:
        return out[:, 0, :]
    return out.reshape(slots, kv_heads, _HEAD_ROWS, width)[:, :, :per].reshape(
        slots, num_heads * width)


# jitted so that the equal-shaped layers of a model share ONE traced and
# lowered kernel (flash_kernel.py: tracing it per layer cost 3.5 s of set-up)
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def paged_decode_attention(q, k_pool, v_pool, table, pos, write_pages,
                           num_heads: int, scale: float,
                           num_kv_heads: int = 0, window: int = 0):
    """``q``: (slots, h * hd), each slot's current-token query, folded;
    ``k_pool`` / ``v_pool``: (num_pages, page, g * hd), the new rows
    already written (``g`` key/value heads, ``num_kv_heads`` or ``h``);
    ``table``: (slots, pages_per_slot) int32; ``pos``: (slots,) int32
    position of the current token; ``write_pages``: (slots,) int32,
    ``num_pages`` where a slot is not decoding -> (slots, h * hd) f32,
    folded, zero for such a slot.  ``window``: read positions ``pos -
    window + 1 .. pos`` only, ``table`` a ring (the module's docstring).
    The caller checks :func:`supported`."""
    return _over_heads(q, k_pool, v_pool, table, pos, write_pages, num_heads,
                       scale, num_kv_heads, window, "paged_decode_attention")


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def paged_sparse_attention(q, k_pool, v_pool, table, pos, write_pages, keep,
                           num_heads: int, scale: float,
                           num_kv_heads: int = 0):
    """:func:`paged_decode_attention` over a CHOSEN SET: ``keep`` (slots,
    pages_per_slot * page) bool, the positions of its table each slot's
    query attends over (past ``pos`` none is read whatever ``keep`` says; a
    decoding slot keeps at least one at or under ``pos``); every live page
    is read once, where it lies -> (slots, h * hd) f32, zero for a slot that
    is not decoding.  ``paged_sparse_attention`` in a device trace (not
    ``paged_decode_attention``: the benchmark finds that kernel by its name
    in other cells).  The caller checks :func:`supported`."""
    return _over_heads(q, k_pool, v_pool, table, pos, write_pages, num_heads,
                       scale, num_kv_heads, 0, "paged_sparse_attention", keep)
