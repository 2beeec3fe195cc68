"""The repo's own kernel for a decoded token's choice of keys
(``ops/paged_index_kernel.py``) on the CPU, in Pallas' interpret mode: its
scores against ``index_scores`` over the gathered view, its threshold against
``select_threshold``, the list ``rows_by_rank`` makes of the chosen set
against ``jax.lax.top_k``'s.  The TPU's compiler sees the kernel and the
token step round it in ``tests/test_generation.py`` (one file holds the
compiles for a described chip); the op's token step under the kernel is in
``tests/test_sparse_attention.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import attention as att
from flexflow_tpu.ops import paged_index_kernel as pk

HEADS, DI, WIDTH = 16, 64, 128

# (dtype, page, pages a slot, topk, each slot's position, slots that do not
# decode, scores that tie): the history against the pages', the chunks' and
# the copy groups' edges
CASES = {
    "one_page_of_history": ("bfloat16", 16, 12, 8, (3, 15, 9), (), False),
    "fewer_live_positions_than_topk": (
        "bfloat16", 16, 40, 64, (20, 62, 5), (), False),
    "history_ends_mid_page_and_mid_chunk": (
        "bfloat16", 16, 80, 64, (700, 515, 1279), (), False),
    "ties_at_the_threshold": ("bfloat16", 16, 40, 64, (100, 639, 300), (),
                              True),
    "ties_and_a_short_history": ("bfloat16", 16, 40, 200, (150, 639, 45), (),
                                 True),
    "a_slot_that_does_not_decode": (
        "bfloat16", 16, 40, 64, (100, 639, 300, 77), (1,), True),
    "no_slot_decodes_first_or_last": (
        "bfloat16", 16, 40, 64, (100, 639, 300, 77, 200), (0, 4), False),
    "several_copy_groups_a_slot": (
        "bfloat16", 16, 300, 256, (4799, 2047, 2048, 4100), (2,), True),
    "a_page_of_one_chunk": ("bfloat16", 512, 3, 100, (1535, 511, 700), (),
                            True),
    "float32_pages_of_eight": ("float32", 8, 30, 16, (239, 17, 100), (),
                               True),
}


def _case(name):
    """A pool whose slots' pages lie OUT OF ORDER, the queries and their
    heads' weights; with ``ties`` every value a small integer or half, so
    that many scores are equal.  Table entries past a slot's position are
    STALE: they name pages another slot holds, full of NaN."""
    dtype, page, pps, topk, pos, dead, ties = CASES[name]
    rng = np.random.default_rng(len(name))
    slots = len(pos)
    pages = slots * pps + 5

    def values(shape):
        x = rng.normal(size=shape)
        return np.round(x) if ties else x

    pool = values((pages, page, WIDTH)).astype(np.float32)
    pool[..., DI:] = 0
    table = rng.permutation(pages)[:slots * pps].reshape(slots, pps)
    pos = np.asarray(pos, np.int32)
    wp = table[np.arange(slots), pos // page].astype(np.int32)
    for s in range(slots):
        past = table[s, pos[s] // page + 1:]
        pool[past] = np.nan
    wp[list(dead)] = pages
    qi = jnp.asarray(values((slots, HEADS, DI)), dtype)
    wi = jnp.asarray(np.round(values((slots, HEADS)) * 2) / 2, jnp.float32)
    return (qi, wi, jnp.asarray(pool, dtype), jnp.asarray(table, jnp.int32),
            jnp.asarray(pos), jnp.asarray(wp), topk, dead)


@jax.jit
def _scores_of_the_view(qi, wi, pool, table, pos):
    """What the op computes without the kernel: every slot's whole view of
    the pool written out, ``index_scores`` over it (jitted, as a serving
    program is), ``NEG_INF`` past ``pos``."""
    slots = table.shape[0]
    view = jnp.take(pool, table, axis=0, mode="clip").reshape(
        slots, -1, WIDTH)[..., :DI]
    scores = att.index_scores(qi[:, None], view, wi[:, None])[:, 0]
    return jnp.where(jnp.arange(scores.shape[1])[None, :] > pos[:, None],
                     att.NEG_INF, scores)


def _run(name):
    qi, wi, pool, table, pos, wp, topk, dead = _case(name)
    got = pk.paged_index_select(qi, wi, pool, table, pos, wp, topk)
    want = np.asarray(_scores_of_the_view(qi, wi, pool, table, pos))
    live = [s for s in range(len(pos)) if s not in dead]
    return [np.asarray(x) for x in got], want, live, topk, dead


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_scores_are_index_scores_bit_for_bit(case):
    """The products in the pool's dtype with f32 accumulation, ``relu``,
    the weighted sum over the heads in f32, one zero only, ``NEG_INF`` past
    ``pos``: the same bits as ``index_scores`` over the gathered view, on
    pages that lie out of order; a page past ``pos`` is never read (it
    holds NaN here); a slot that does not decode reads ``NEG_INF``
    everywhere."""
    (scores, _, _), want, live, _, dead = _run(case)
    assert scores.dtype == np.float32 and scores.shape == want.shape
    assert np.isfinite(scores).all()
    if CASES[case][0] == "float32":
        # (the CPU's f32 product of a 128-wide row rounds another way than
        # its 8-wide einsum: to an ulp)
        np.testing.assert_allclose(scores[live], want[live], rtol=2e-6,
                                   atol=2e-6)
    else:
        assert (scores[live] == want[live]).all()
    assert (scores[list(dead)] == att.NEG_INF).all()
    assert not np.signbit(scores[scores == 0]).any()


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_threshold_is_select_thresholds(case):
    """``thr`` and ``last`` of every decoding slot are ``select_threshold``'s
    of that slot's row alone (the second search runs for a row only where
    ITS ties pass its need); a slot that does not decode names the first
    ``topk`` positions."""
    (scores, thr, last), _, live, topk, dead = _run(case)
    for s in live:
        t, l = att.select_threshold(jnp.asarray(scores[s])[None], topk)
        assert thr[s] == np.asarray(t)[0] and last[s] == int(l[0]), s
    for s in dead:
        keep = np.asarray(att.selected(
            jnp.asarray(scores[s]), jnp.arange(scores.shape[1]),
            jnp.asarray(thr[s]), jnp.asarray(last[s])))
        assert np.flatnonzero(keep).tolist() == list(range(topk))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_row_list_is_top_ks_set(case):
    """``selected`` under the kernel's threshold, listed by
    ``rows_by_rank``: exactly ``topk`` positions a slot, ascending, and of a
    decoding slot the SET ``jax.lax.top_k`` names on the view's scores (of
    equal scores the lower position; dead positions where the history is
    shorter than ``topk``)."""
    (scores, thr, last), want, live, topk, _ = _run(case)
    keep = att.selected(jnp.asarray(scores), jnp.arange(scores.shape[1]),
                        jnp.asarray(thr), jnp.asarray(last))
    idx = np.asarray(pk.rows_by_rank(keep, topk))
    assert idx.shape == (scores.shape[0], topk) and idx.dtype == np.int32
    assert (np.diff(idx, axis=1) > 0).all()
    _, top = jax.lax.top_k(jnp.asarray(want), topk)
    for s in live:
        assert sorted(np.asarray(top[s]).tolist()) == idx[s].tolist(), s


@pytest.mark.parametrize("n,L,topk", [
    (1, 12, 3), (3, 128, 128), (2, 300, 1), (4, 1000, 257),
    (2, 25088, 2048), (2, 1280, 700), (1, 384, 300)])
def test_rows_by_rank_lists_any_mask_of_topk_positions(n, L, topk):
    """Random masks of exactly ``topk`` positions a row, lengths that are
    no whole number of blocks, one to 196 blocks: the list is
    ``np.flatnonzero`` of each row."""
    rng = np.random.default_rng(L + topk)
    keep = np.zeros((n, L), bool)
    for row in keep:
        row[rng.choice(L, topk, replace=False)] = True
    keep[0, :] = False
    keep[0, L - topk:] = True       # the last positions, a block's tail
    idx = np.asarray(pk.rows_by_rank(jnp.asarray(keep), topk))
    assert idx.tolist() == [np.flatnonzero(row).tolist() for row in keep]

