#!/usr/bin/env python3
"""Times the forms of attention with a learned selection on the chip, at the
widths of ``perfbench/configs/keye-vl-2.0-30b-a3b.json``:

    chiprun --chips 1 -- python3 scripts/sparse_attention_forms.py

One layer's whole step (projections, indexer, cache writes, scores, choice,
core, output) against a 25 088-position table, pages of 16:

* a 512-token CHUNK whose last row stands at ``L`` = 4 096, 12 288 and
  24 576, the threshold of a row's 2 048 best found two ways: ``sorted``
  (``jax.lax.top_k``, which the TPU's compiler lowers to a full sort of
  every row) and ``bits`` (the 2 048-th largest found bit by bit on the
  scores' order-preserving integer image: 32 counting passes over the
  ``(512, 25 088)`` buffer and, only where scores tie AT the threshold, 15
  more for the last tied position chosen).  Both are exact and give the same
  set; the tree keeps one as ``ops/attention.select_threshold`` and the
  other is written HERE only;
* a TOKEN step of 24 slots of which 13 decode, at positions 8 191-24 575.
  The core three ways: ``paged`` (every live page read once, where it
  lies, by the paged decode kernel under the chosen set as a MASK: what the
  op does on a TPU for a table of no more pages than it chooses rows, so
  here), ``rows`` (the 2 048 chosen rows a slot copied out of the pools,
  then dense attention over them: what it does for a longer table, held to
  it HERE by a subclass) and ``gathered`` (each slot's whole page table
  written out as a view and masked: what it does elsewhere).  And under
  ``rows`` the CHOICE five ways: ``view + sort`` (every slot's view of
  ``ik`` written out, its scores sorted by ``jax.lax.top_k``: the op before
  PR 46, written HERE only), ``paged + sort``
  (``ops/paged_index_kernel.py``'s scores, read from the pool in place,
  then ``top_k``), and ``paged + threshold`` (the kernel's scores AND its
  threshold search) with the list made in XLA (``rows_by_rank``, the pages
  by a gather from the table: the op's ``rows``; or the pages by a one-hot
  product too, written HERE only) or in a second kernel that is written
  HERE only;
* the three cores again where reading pages costs most and least: 13 of 24
  AND 24 of 24 slots decoding, every slot at position 4 095, spread over
  8 191-24 575, and every slot at 24 575 (all slots at the full history is
  the ``paged`` form's WORST case in this table: about 37 600 live pages a
  layer, where ``rows`` reads 49 152 rows whatever the history);
* the CHOICE alone at those slots and positions, on scores that seldom tie
  and on scores that often do: the kernel's threshold and ``rows_by_rank``
  must name the SET ``jax.lax.top_k`` names on the kernel's own scores
  (asserted; so must the one-hot's pages be the table's); the kernel's scores
  against XLA's over the view are printed (the same arithmetic summed in
  another order on the chip, so not the same bits, and of positions that
  all but tie another may be the 2 048-th).

The tree keeps the forms that win (PERF.md section 5 has the numbers); this
script is how to ask again.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.op import OpContext, ServeStep
from flexflow_tpu.ops import attention as att
from flexflow_tpu.ops import paged_index_kernel as pk
from flexflow_tpu.ops.flash_kernel import _dot
from flexflow_tpu.tensor import Tensor


def threshold_sorted(scores, topk):
    """:func:`flexflow_tpu.ops.attention.select_threshold`'s contract by
    ``jax.lax.top_k``: of equal scores it puts the lower position first."""
    vals, idx = jax.lax.top_k(scores, topk)
    thr = vals[..., -1]
    last = jnp.max(jnp.where(vals == thr[..., None], idx, -1), axis=-1)
    return thr, last


class Gathered(att.MultiHeadAttention):
    """The same op held to the masked view in its token step."""

    def _decode_core(self, pool, ctx):
        return "gathered"


class Rows(att.MultiHeadAttention):
    """The same op held to the chosen rows' copies in its token step, where
    the table's shape would have it read pages."""

    def _token_form(self, pool, table, ctx):
        form = super()._token_form(pool, table, ctx)
        return form if form == "gathered" else "rows"


class ViewSort(Rows):
    """The parent's choice: every slot's view of ``ik`` written out, the
    scores from the view, ``jax.lax.top_k``, the pages by a gather."""

    def _chosen_rows(self, qi, wi, i_pool, where):
        n, page = qi.shape[0], i_pool.shape[1]
        with jax.named_scope("dsa_index"):
            view = jnp.take(i_pool, where.table, axis=0, mode="clip")
            view = view.reshape(n, -1, i_pool.shape[2])[..., :self.index_dim]
            scores = att.index_scores(qi, view, wi)[:, 0]          # (n, L)
            scores = jnp.where(jnp.arange(scores.shape[1])[None, :]
                               > where.pos[:, None], att.NEG_INF, scores)
        with jax.named_scope("dsa_select"):
            vals, idx = jax.lax.top_k(scores, self.topk)
            alive = vals > att.NEG_INF / 2
            pid = jnp.take_along_axis(where.table, idx // page, axis=1)
            return idx, pid, alive, jnp.sum(alive & where.live(1))


class _KernelsScores(Rows):
    """The kernel's scores and threshold (``ik`` read in place), and a list
    made of them by ``_listed`` in place of ``rows_by_rank``; the pages by a
    gather from the table."""

    def _chosen_rows(self, qi, wi, i_pool, where):
        with jax.named_scope("dsa_index"):
            scores, thr, last = pk.paged_index_select(
                qi[:, 0], wi[:, 0], i_pool, where.table, where.pos,
                where.write_pages, self.topk)
        with jax.named_scope("dsa_select"):
            idx = self._listed(scores, thr, last)
            alive = (idx <= where.pos[:, None]) & where.live(1)
            pid = jnp.take_along_axis(where.table, idx // i_pool.shape[1],
                                      axis=1)
            return idx, pid, alive, jnp.sum(alive)


class PagedSort(_KernelsScores):
    """``jax.lax.top_k`` on the kernel's scores: the first half of the
    change alone."""

    def _listed(self, scores, thr, last):
        return jax.lax.top_k(scores, self.topk)[1]


def pages_by_one_hot(keep, idx, table, page):
    """Each listed position's page WITHOUT a gather (XLA's goes an element
    at a time on a TPU: ``take_along_axis`` of the table by 24 x 2 048
    positions read 2.0 ms a token step): slot ``j`` of the list finds its
    block of 128 positions by ``rows_by_rank``'s one-hot over the blocks
    and takes that block's page ids by a product with it, each id as four
    base-256 digits so that bfloat16 operands are exact."""
    n, L = keep.shape
    topk, lanes = idx.shape[1], 128
    blocks = -(-L // lanes)
    count = jnp.sum(jnp.pad(keep, ((0, 0), (0, blocks * lanes - L))).reshape(
        n, blocks, lanes), axis=-1, dtype=jnp.int32)
    offset = jnp.cumsum(count, axis=-1) - count
    j = jnp.arange(topk)[None, :, None]
    mine = (offset[:, None, :] <= j) & (j < (offset + count)[:, None, :])
    # a block's pages (one entry repeated where a page holds several blocks)
    per_block = max(1, lanes // page)
    entries = jnp.repeat(table, max(1, page // lanes), axis=1)
    entries = jnp.pad(entries, ((0, 0), (0, max(
        0, blocks * per_block - entries.shape[1]))))[:, :blocks * per_block]
    entries = entries.reshape(n, blocks, per_block)
    digits = jnp.concatenate([(entries >> (8 * d)) & 255 for d in range(4)],
                             axis=-1).astype(jnp.bfloat16)
    ids = jnp.einsum("njb,nbl->njl", mine.astype(jnp.bfloat16), digits,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    ids = ids.reshape(n, topk, 4, per_block)
    ids = sum(ids[:, :, d] << (8 * d) for d in range(4))
    return jnp.sum(jnp.where(
        jnp.arange(per_block) == (idx % lanes // page)[..., None], ids, 0),
        axis=-1).astype(jnp.int32)


class PagesByOneHot(Rows):
    """The op's choice with each row's page by :func:`pages_by_one_hot` in
    place of the gather from the table (written here only)."""

    def _chosen_rows(self, qi, wi, i_pool, where):
        with jax.named_scope("dsa_index"):
            scores, thr, last = pk.paged_index_select(
                qi[:, 0], wi[:, 0], i_pool, where.table, where.pos,
                where.write_pages, self.topk)
        with jax.named_scope("dsa_select"):
            keep = att.selected(scores, jnp.arange(scores.shape[1]), thr,
                                last)
            idx = pk.rows_by_rank(keep, self.topk)
            alive = (idx <= where.pos[:, None]) & where.live(1)
            pid = pages_by_one_hot(keep, idx, where.table, i_pool.shape[1])
            return idx, pid, alive, jnp.sum(alive)


def _rank_kernel(thr_ref, last_ref, s_ref, o_ref, *, positions, piece):
    """:func:`paged_index_kernel.rows_by_rank` with a slot's mask, ranks
    and one-hots in VMEM (one grid step a slot): the list as a ``(1,
    topk)`` row, the list's slots along the lanes, ``piece`` of them at a
    time."""
    i = pl.program_id(0)
    s = s_ref[0]                                            # (R, 128) f32
    R, lanes = s.shape
    topk = o_ref.shape[2]
    f32, bf16 = jnp.float32, jnp.bfloat16

    def iota(shape, dim, dtype=jnp.int32):
        return jax.lax.broadcasted_iota(dtype, shape, dim)

    kpos = iota((R, lanes), 0) * lanes + iota((R, lanes), 1)
    thr, last = thr_ref[i], last_ref[i]
    keep = ((s > thr) | ((s == thr) & (kpos <= last))) & (kpos < positions)
    kept = jnp.where(keep, 1.0, 0.0).astype(bf16)
    under = jnp.where(iota((lanes, lanes), 0) < iota((lanes, lanes), 1),
                      1.0, 0.0).astype(bf16)
    inner = jnp.where(keep, _dot(kept, under), -1.0)     # in-block ranks
    count = _dot(kept, jnp.ones((lanes, lanes), bf16))   # every lane
    below = jnp.where(iota((R, R), 1) < iota((R, R), 0), 1.0, 0.0
                      ).astype(bf16)
    offset = _dot(below, count.astype(bf16))[:, :1]      # (R, 1)
    count = count[:, :1]
    inner_t = inner.T.astype(bf16)                          # (128, R)
    for t in range(topk // piece):
        j = (t * piece + iota((R, piece), 1)).astype(f32)
        mine = (offset <= j) & (j < offset + count)         # (R, piece)
        ranks = _dot(inner_t, jnp.where(mine, 1.0, 0.0).astype(bf16))
        start = jnp.sum(jnp.where(mine, offset, 0.0), axis=0, keepdims=True)
        which = jnp.sum(jnp.where(mine, iota((R, piece), 0).astype(f32),
                                  0.0), axis=0, keepdims=True)
        here = ranks == j[:1] - start                       # (128, piece)
        lane = jnp.sum(jnp.where(here, iota((lanes, piece), 0).astype(f32),
                                 0.0), axis=0, keepdims=True)
        o_ref[0, :, t * piece:(t + 1) * piece] = (
            which * lanes + lane).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(3,))
def rows_by_rank_in_a_kernel(scores, thr, last, topk):
    slots, positions = scores.shape
    rows = -(-positions // (128 * 128)) * 128     # whole lane tiles of blocks
    s = jnp.pad(scores, ((0, 0), (0, rows * 128 - positions)),
                constant_values=att.NEG_INF).reshape(slots, rows, 128)
    out = pl.pallas_call(
        functools.partial(_rank_kernel, positions=positions,
                          piece=min(512, topk)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots,),
            in_specs=[pl.BlockSpec((1, rows, 128), lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, topk), lambda i, *_: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((slots, 1, topk), jnp.int32),
        compiler_params=None if pk._interpret() else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=pk._interpret(), name="paged_index_rank",
    )(thr, last, s)
    return out[:, 0]


class PagedKernelList(_KernelsScores):
    """The list made by a SECOND kernel (written here only)."""

    def _listed(self, scores, thr, last):
        return rows_by_rank_in_a_kernel(scores, thr, last, self.topk)


TOKEN_FORMS = {
    "view + sort (the parent's)": ViewSort,
    "paged + sort": PagedSort,
    "paged + threshold, list in XLA": Rows,
    "paged + threshold, pages by a one-hot": PagesByOneHot,
    "paged + threshold, list in a kernel": PagedKernelList,
    "gathered (the CPU's)": Gathered,
    "pages under the set as a mask": att.MultiHeadAttention,
}
CORES = {"rows": Rows, "gathered": Gathered, "paged": att.MultiHeadAttention}


def _timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(ts)), out


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        cfg = json.load(f)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind!r}",
          flush=True)
    small = dev.platform != "tpu"      # a CPU rehearsal of the script
    sa = cfg["sa_config"]
    d = 128 if small else cfg["hidden_size"]
    heads, kv_heads = (4, 2) if small else (cfg["num_attention_heads"],
                                            cfg["num_key_value_heads"])
    hd = 16 if small else cfg["head_dim"]
    topk = 64 if small else sa["topk"]
    chunk, page = (64, 16) if small else (512, 16)
    max_seq = 1024 if small else cfg["run"]["max_seq"]
    slots = 4 if small else 24
    pps = max_seq // page
    pages = slots * pps
    ctx = OpContext(training=False, compute_dtype="bfloat16", mesh=None)
    key = jax.random.PRNGKey(0)

    def make(cls, n, w):
        x = Tensor(shape=(n, w, d), dtype="float32", name="x")
        op = cls("attention_0", x, x, x, d, heads, causal=True,
                 use_bias=False, num_kv_heads=kv_heads, head_dim=hd,
                 rope={"rope_theta": cfg["rope_theta"]},
                 qk_norm=cfg["rms_norm_eps"],
                 sparse={"index_heads": sa["indexer_num_heads"],
                         "index_dim": sa["indexer_head_dim"], "topk": topk})
        params = {p.name: (jnp.ones(p.shape, jnp.bfloat16)
                           if p.name.endswith("norm") else
                           (0.02 * jax.random.normal(
                               jax.random.fold_in(key, i), p.shape,
                               jnp.float32)).astype(jnp.bfloat16))
                  for i, p in enumerate(op.weights)}
        return op, params

    def pools(op):
        def pool(i, width):
            return (0.5 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), (pages, page, width),
                jnp.float32)).astype(jnp.bfloat16)
        ik = pool(2, op.index_width).at[..., op.index_dim:].set(0)
        return {"k": pool(0, op.kv_dim), "v": pool(1, op.kv_dim), "ik": ik}

    results = {}
    # ---- the chunk, its threshold two ways -------------------------------
    op, params = make(att.MultiHeadAttention, 1, chunk)
    state = pools(op)
    table = jnp.arange(pps, dtype=jnp.int32)
    rows = (0.5 * jax.random.normal(key, (1, chunk, d), jnp.float32)
            ).astype(jnp.bfloat16)
    kept = att.select_threshold
    for name, fn in (("bits", kept), ("sorted", threshold_sorted)):
        att.select_threshold = fn

        @jax.jit
        def step(params, rows, state, start):
            out, new = op.serve_step(params, [rows], state, ServeStep(
                "chunk", table, start=start, length=jnp.int32(chunk),
                slot=jnp.int32(0), no_page=pages), ctx)
            return out[0]

        for L in ((256, 1024) if small else (4096, 12288, 24576)):
            ms, out = _timed(step, params, rows, state, jnp.int32(L - chunk))
            results[name, L] = (ms, np.asarray(out, np.float32))
            print(f"chunk {name:8s} L={L:6d}: {ms:8.3f} ms "
                  f"({op.chunk_core})", flush=True)
    att.select_threshold = kept
    for L in ((256, 1024) if small else (4096, 12288, 24576)):
        a, b = results["bits", L][1], results["sorted", L][1]
        print(f"chunk L={L}: largest difference between the two "
              f"{float(np.abs(a - b).max()):.3g}", flush=True)
    # ---- the token step: where the choice is made, and the core ----------
    # 24 slots of which 13 decode (the cell's ``slot_occupancy`` 53 %), at
    # positions 8 191-24 575; every form's whole step, and the outputs of
    # the decoding slots against the parent's form
    pos = jnp.asarray(np.linspace(max_seq // 3, max_seq - chunk - 1,
                                  slots).astype(np.int32))
    tab = jnp.arange(pages, dtype=jnp.int32).reshape(slots, pps)
    decoding = np.arange(slots) % 2 == 0
    decoding[1] = True                          # 13 of 24
    wp = jnp.where(jnp.asarray(decoding), jnp.take_along_axis(
        tab, (pos // page)[:, None], axis=1)[:, 0], pages)
    tok = (0.5 * jax.random.normal(key, (slots, 1, d), jnp.float32)
           ).astype(jnp.bfloat16)
    def token_step(cls, pos, wp):
        """``(ms, outputs, the op)`` of one form's whole token step."""
        op, params = make(cls, slots, 1)
        if small and cls is not Gathered:   # a rehearsal: the interpreter's
            op._decode_core = lambda pool, ctx: "paged"
        state = pools(op)

        @jax.jit
        def step(params, tok, state):
            out, new = op.serve_step(params, [tok], state, ServeStep(
                "token", tab, pos=pos, write_pages=wp, write_rows=pos % page,
                no_page=pages), ctx)
            return out[0]

        ms, out = _timed(step, params, tok, state)
        return ms, np.asarray(out, np.float32), op

    outs = {}
    for name, cls in TOKEN_FORMS.items():
        ms, out, op = token_step(cls, pos, wp)
        outs[name] = out[decoding]
        print(f"token {name:36s} ({op.decode_core}): {ms:8.3f} ms",
              flush=True)
        if not small and cls in (att.MultiHeadAttention, Rows):
            assert op.decode_core == ("rows" if cls is Rows else "paged")
    for against in ("view + sort (the parent's)", "paged + sort"):
        for name, out in outs.items():
            print(f"token {name:36s}: largest difference from {against!r} "
                  f"{float(np.abs(out - outs[against]).max()):.3g} (outputs "
                  f"up to {float(np.abs(out).max()):.3g})", flush=True)
    # ---- the three cores, where pages cost most and least ----------------
    short, last = (127 if small else 4095), max_seq - chunk - 1
    for count in (int(decoding.sum()), slots):
        busy = np.ones(slots, bool) if count == slots else decoding
        for label, at in ((f"all at {short}", np.full(slots, short)),
                          (f"{max_seq // 3}-{last}", np.asarray(pos)),
                          (f"all at {last}", np.full(slots, last))):
            at = jnp.asarray(at.astype(np.int32))
            to = jnp.where(jnp.asarray(busy), jnp.take_along_axis(
                tab, (at // page)[:, None], axis=1)[:, 0], pages)
            got = {name: token_step(cls, at, to)
                   for name, cls in CORES.items()}
            live_pages = int(np.sum((np.asarray(at) // page + 1)[busy]))
            print(f"cores, {count:2d} of {slots} decode, positions "
                  f"{label:12s} ({live_pages:6d} live pages): " + ", ".join(
                      f"{name} {ms:7.3f} ms" for name, (ms, _, _)
                      in got.items()) + "; largest difference from rows: "
                  + ", ".join(
                      f"{name} "
                      f"{float(np.abs(out - got['rows'][1])[busy].max()):.3g}"
                      for name, (_, out, _) in got.items()
                      if name != "rows"), flush=True)
    # ---- the choice alone: one row of scores, one set --------------------
    di, width = op.index_dim, op.index_width
    wi = jax.random.normal(jax.random.fold_in(key, 7), (slots, op.index_heads),
                           jnp.float32)

    @jax.jit
    def choice(qi, wi, ik):
        scores, thr, last = pk.paged_index_select(qi, wi, ik, tab, pos, wp,
                                                  topk)
        keep = att.selected(scores, jnp.arange(scores.shape[1]), thr, last)
        idx = pk.rows_by_rank(keep, topk)
        pid = pages_by_one_hot(keep, idx, tab, page)
        view = jnp.take(ik, tab, axis=0).reshape(slots, -1, width)[..., :di]
        of_view = att.index_scores(qi[:, None], view, wi[:, None])[:, 0]
        of_view = jnp.where(jnp.arange(of_view.shape[1])[None, :]
                            > pos[:, None], att.NEG_INF, of_view)
        return (scores, idx, pid, jax.lax.top_k(scores, topk)[1],
                jax.lax.top_k(of_view, topk)[1], of_view)

    for ties in (False, True):
        def values(i, shape):
            x = jax.random.normal(jax.random.fold_in(key, 200 + i), shape,
                                  jnp.float32)
            return (jnp.round(x) if ties else 0.5 * x).astype(jnp.bfloat16)

        ik = values(0, (pages, page, width)).at[..., di:].set(0)
        got = choice(values(1, (slots, op.index_heads, di)),
                     jnp.round(2 * wi) / 2 if ties else wi, ik)
        scores, idx, pid, best, best_of_view, of_view = (
            np.asarray(x)[decoding] for x in got)
        assert (np.sort(best, axis=1) == idx).all(), ties
        # (the form written here only: the op gathers its pages)
        assert (pid == np.take_along_axis(np.asarray(tab)[decoding],
                                          idx // page, 1)).all(), ties
        other = np.mean([len(set(a) - set(b))
                         for a, b in zip(best_of_view, idx)])
        live = scores > att.NEG_INF / 2
        tied = np.mean([(r == np.sort(r)[-topk]).sum() for r in scores])
        print(f"choice (ties {ties}): the threshold and the list by rank "
              f"name top_k's set of the kernel's scores (and the "
              f"one-hot's pages are the table's); tied at the threshold "
              f"{tied:.1f} a slot; "
              f"kernel's scores against the view's: largest "
              f"difference {np.abs(scores - of_view)[live].max():.3g} of "
              f"scores up to {np.abs(scores[live]).max():.3g}, "
              f"{other:.2f} of {topk} positions a slot chosen otherwise",
              flush=True)


if __name__ == "__main__":
    main()
