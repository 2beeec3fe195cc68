"""The repo's own Pallas grouped matmul for TPU: the experts' products over
rows SORTED by group, at the cost of the rows there are.

The contract is ``jax.lax.ragged_dot(lhs (A, K), rhs (G, K, N), group_sizes
(G,), preferred_element_type=float32)``: rows ``sum(sizes[:g]) ..
sum(sizes[:g + 1])`` of ``lhs`` times ``rhs[g]``, operands in the dtype they
arrive in (the MXU takes bfloat16 in one pass), accumulated in float32.  Rows
past ``sum(group_sizes)`` are UNDEFINED here (``MoE._experts`` masks them).

Why not the library's.  On a v5e ``ragged_dot`` is XLA's own grouped-matmul
kernel, tiled 512 x 512 x 512: every group computes on at least 512 rows.  A
token step of 128 slots x 8 choices over 256 experts gives a group 4 rows, a
512-token prompt chunk 16, so 97-99 % of what that kernel multiplied was
padding and its time was per GROUP, 18.5 ms for the four sparse layers
whatever the rows (PERF.md section 6, PR 36).  With that few rows a group
the floor is the BYTES of the experts touched, each read once.

**The walk** (the MegaBlocks form).  Rows are cut into tiles of ``tm`` (16
to 128 rows, from the static ``A``: :func:`row_tile`), and the kernel
VISITS every (row tile, group) pair that shares a row, in row order:
a tile that holds rows of three groups is visited three times, a group that
spans two tiles twice, a group with no row never.  :func:`visits` builds the
walk from ``group_sizes`` inside the jit (a cumulative sum and one compare,
a few hundred integers) and it reaches the kernel by scalar prefetch; its
length is static, ``A / tm + G - 1`` at most, and the visits past the last
real one repeat it (no copy moves, nothing is computed).

**A group's weights are fetched once.**  The block of ``rhs`` is ``(K,
tn)``: ``K`` whole, so no partial sums are carried, ``N`` in tiles of ``tn``
lanes (the largest that keeps a block at ``_BLOCK_BYTES``).  The grid is
``(N / tn, visits)`` with the walk innermost, so consecutive visits of one
group keep the block index and Pallas' pipeline copies nothing; a group
nobody chose is in no visit and costs no byte (what
``moe_decode_roofline``'s floor assumes).  ``rhs`` is read where it lies,
``(groups, in, out)``: no transposed, padded or re-tiled copy.

**A visit** multiplies the whole row tile by the group's block and keeps,
of the ``(tm, tn)`` result, the rows that are the group's (a select against
the output block, which stays in VMEM while consecutive visits share its
row tile).  Rows of a visited tile that belong to no group keep what the
buffer held; tiles past the last group are never written.

The kernel's ``name=`` is ``ragged-dot-rows``: ``perfbench/flops/laguna.py``
finds the grouped products by ``MOE_KERNELS = r"^ragged-dot"``, as it found
the library's ``ragged-dot*`` (``moe_share``, ``moe_decode_roofline``), and
the kernel IS a ragged dot.  The walk's few integer fusions are not in that
pattern, as the sort and the combine never were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import dtype_itemsize
from .flash_kernel import LANES, _dot, _interpret

_ROW_TILES = (128, 64, 32, 16)  # down to one bf16 sublane tile
_LIBRARY_ROWS = 512         # the row tile of XLA's own grouped matmul
_BLOCK_BYTES = 4 << 20      # one (K, tn) block of a group's weights
_VMEM_LIMIT = 32 << 20


def row_tile(rows: int) -> int:
    """Rows of a tile, from the static shape: the largest of 128 .. 16 that
    divides ``rows``, 0 if none does.  With a handful of rows a group a
    visit's time is the MXU taking the group's weights in, the same for 16
    rows as for 128, so a larger tile only saves the second visits of the
    groups that straddle a tile boundary (in the serve cell, tiles of 128
    and blocks of 4 MB against 16-32 and 2 MB: a token step's products
    8.88 -> 8.25 ms, a chunk's 9.71 -> 8.53 ms; my chip runs, PR 37)."""
    return next((tm for tm in _ROW_TILES if rows % tm == 0), 0)


def _lane_tile(k: int, n: int, itemsize: int) -> int:
    """Lanes of a weight block: the most 128-lane tiles that divide ``n``
    and keep ``(k, tn)`` within ``_BLOCK_BYTES``, 0 if not even one does;
    an ``n`` that is not whole lane tiles (the interpreter's tests) is
    taken whole."""
    if n % LANES:
        return n
    fits = _BLOCK_BYTES // (k * itemsize) // LANES
    tiles = n // LANES
    return LANES * max((t for t in range(1, min(fits, tiles) + 1)
                        if tiles % t == 0), default=0)


def supported(backend: str, dtype, rows: int, groups: int, k: int, n: int,
              distributed: bool = False, training: bool = False) -> bool:
    """What the kernel needs, and where it is the right one (``dtype``:
    both operands', an array's ``.dtype``): a TPU; operands the MXU takes;
    ``k`` and ``n`` whole 128-lane tiles with one ``(k, 128)`` block inside
    the budget; ``rows`` whole row tiles; FEWER rows a group in the mean
    than the library kernel's 512-row tile (a batch that gives every expert
    thousands of rows keeps the library kernel, which is right for it);
    no gradient (the kernel is forward-only and nothing trains a mixture
    on a chip yet); and ONE device — under an ``e`` axis of several shards,
    or any mesh GSPMD partitions, the answer is no until a chip run can
    show it (nothing serves across chips: ROADMAP M1)."""
    if (backend != "tpu" or distributed or training
            or dtype not in (jnp.bfloat16, jnp.float32)):
        return False
    return (k % LANES == 0 and n % LANES == 0
            and _lane_tile(k, n, dtype_itemsize(dtype)) > 0
            and row_tile(rows) > 0 and rows < _LIBRARY_ROWS * groups)


@functools.partial(jax.jit, static_argnums=(1,))
def visits(group_sizes, rows: int):
    """The walk over ``rows`` sorted rows: ``(offsets (G + 1,), group (V,),
    tile (V,), total (1,))`` int32, ``V = rows / tm + G - 1``.  Visit ``v <
    total`` is row tile ``tile[v]`` against group ``group[v]``, whose rows
    are ``offsets[g] .. offsets[g + 1]``; a group with rows in tiles ``a ..
    b`` has ``b - a + 1`` consecutive visits, an empty group none, and
    ``v >= total`` repeats the last real visit.  The two products of one
    layer share one walk."""
    groups = group_sizes.shape[0]
    tm = row_tile(rows)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)            # visits of groups 0 .. g
    total = upto[-1]
    v = jnp.minimum(jnp.arange(rows // tm + groups - 1, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    group = jnp.minimum(
        jnp.sum((upto[None, :] <= v[:, None]).astype(jnp.int32), axis=1),
        groups - 1)
    tile = jnp.clip(first[group] + v - (upto[group] - tiles[group]),
                    0, rows // tm - 1)
    offsets = jnp.concatenate([starts, ends[-1:]])
    return offsets, group, tile, total[None]


def _kernel(offsets_ref, group_ref, tile_ref, total_ref, x_ref, w_ref, o_ref):
    v = pl.program_id(1)

    @pl.when(v < total_ref[0])
    def _():
        g = group_ref[v]
        acc = _dot(x_ref[...], w_ref[...])                     # (tm, tn)
        row = tile_ref[v] * acc.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = jnp.logical_and(row >= offsets_ref[g],
                               row < offsets_ref[g + 1])
        o_ref[...] = jnp.where(mine, acc, o_ref[...])


# jitted so that the equal-shaped sparse layers of a model share ONE traced
# and lowered kernel (flash_kernel.py: tracing it per layer cost 3.5 s)
@jax.jit
def _products(lhs, rhs, offsets, group, tile, total):
    rows, k = lhs.shape
    groups, _, n = rhs.shape
    tm = row_tile(rows)
    tn = _lane_tile(k, n, rhs.dtype.itemsize)
    params = None if _interpret() else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, group.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, o, g, t, c: (t[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, o, g, t, c: (g[v], 0, j))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, o, g, t, c: (t[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=params, interpret=_interpret(),
        name="ragged-dot-rows",
    )(offsets, group, tile, total, lhs, rhs)


def ragged_dot_rows(lhs, rhs, group_sizes, walk=None):
    """``lhs``: (A, K) rows sorted by group; ``rhs``: (G, K, N);
    ``group_sizes``: (G,) int32 -> (A, N) f32, row ``r`` of group ``g``
    being ``lhs[r] @ rhs[g]``; rows past ``sum(group_sizes)`` undefined.
    ``walk``: :func:`visits` of these sizes, where a caller has two
    products over them.  The caller checks :func:`supported`."""
    if walk is None:
        walk = visits(group_sizes, lhs.shape[0])
    return _products(lhs, rhs, *walk)
