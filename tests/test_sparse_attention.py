"""Attention with a learned selection of keys (``MultiHeadAttention(sparse=,
qk_norm=)``, ISSUE 44): the op against its own dense self, the exactness of
the choice, the forms of its serving step against one another, and the third
cache leaf under prefix reuse, speculation and migration.  The family's
reference is held to the graph in ``tests/perfbench/test_perfbench_keye.py``.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu import GenerationEngine, MachineMesh
from flexflow_tpu.op import OpContext, ServeStep
from flexflow_tpu.ops import attention as att
from flexflow_tpu.serving.generation.decoder import GraphDecoder
from flexflow_tpu.tensor import Tensor

from test_generation import VOCAB, _build_latent_lm, reference_decode

SEQ, CHUNK, TOPK = 48, 8, 8
SPARSE = {"index_heads": 2, "index_dim": 8, "topk": TOPK}
LAYERS = [{"attention": "full_attention", "heads": 4, "mlp": "sparse"}] * 2


def _build(sparse=SPARSE, qk_norm=1e-6, seq=SEQ, chunk=CHUNK, seed=0,
           weights=True, page=4):
    """Two layers of grouped attention (4 query heads over 2 of 8) with
    QK-norm and an indexer of 2 heads of 8 that keeps 8 keys, then 8
    experts top-2 with no shared one; float32, pages of 4."""
    from flexflow_tpu.models import build_decoder_lm
    cfg = ff.FFConfig(batch_size=2, compute_dtype="float32", seed=seed)
    cfg.serve_gen_slots = 2
    cfg.serve_gen_max_seq = seq
    cfg.serve_prefill_chunk = chunk
    cfg.serve_kv_page = page
    model = build_decoder_lm(
        cfg, LAYERS, d_model=32, head_dim=8, num_kv_heads=2, d_ff=64,
        vocab_size=VOCAB, seq_len=seq, qk_norm=qk_norm, sparse=sparse,
        rope={"full_attention": {"rope_theta": 1e4}},
        moe={"num_experts": 8, "k": 2, "d_ff": 16, "shared_d_ff": 0})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    if weights:
        model.init_layers(seed=seed)
    return model


@pytest.fixture(scope="module")
def sparse_lm():
    return _build()


def _serve(model, prompts, new=10, **kw):
    """One request after another (a later prompt finds the earlier one's
    pages in the prefix cache)."""
    with ff.fflogger.silenced("serve"):
        with GenerationEngine(model, slots=2, **kw) as eng:
            outs = [[int(t) for t in eng.submit(
                p, max_new_tokens=new).result(timeout=300)] for p in prompts]
            return outs, eng.stats()


# ---------------------------------------------------------------------------
# the choice itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", range(12))
def test_the_threshold_search_chooses_what_a_stable_sort_chooses(case):
    """``select_threshold`` + ``selected`` against a stable argsort of the
    negated scores (of equal scores the lower position first), on rows full
    of ties, zeros of both signs and positions a row may not see."""
    rng = np.random.default_rng(case)
    L = int(rng.integers(9, 200))
    k = int(rng.integers(1, L))
    x = rng.normal(size=(3, 2, L)).astype(np.float32)
    x = np.round(x * rng.choice([1, 2, 50])) / 2
    x[rng.random(x.shape) < 0.2] = att.NEG_INF
    x[rng.random(x.shape) < 0.1] = -0.0
    thr, last = att.select_threshold(jnp.asarray(x), k)
    keep = np.asarray(att.selected(jnp.asarray(x), jnp.arange(L), thr, last))
    want = np.zeros_like(keep)
    for idx in np.ndindex(3, 2):
        want[idx][np.argsort(-(x[idx] + 0.0), kind="stable")[:k]] = True
    assert (keep == want).all()
    assert (keep.sum(-1) == k).all()


def test_the_tie_rule_takes_the_lower_position():
    """Five equal scores and room for two of them: positions 1 and 4."""
    x = jnp.asarray([[3.0, 1.0, 5.0, 0.5, 1.0, 1.0, 4.0, 1.0, 1.0]])
    thr, last = att.select_threshold(x, 5)
    keep = np.asarray(att.selected(x, jnp.arange(9), thr, last))[0]
    assert float(thr[0]) == 1.0 and int(last[0]) == 4
    assert keep.tolist() == [True, True, True, False, True, False, True,
                             False, False]


def test_index_scores_are_relu_weighted_sums_in_float32():
    rng = np.random.default_rng(3)
    qi = rng.normal(size=(2, 5, 3, 4)).astype(np.float32)
    ki = rng.normal(size=(2, 7, 4)).astype(np.float32)
    wi = rng.normal(size=(2, 5, 3)).astype(np.float32)
    got = np.asarray(att.index_scores(jnp.asarray(qi), jnp.asarray(ki),
                                      jnp.asarray(wi)))
    want = np.einsum("nqh,nqhk->nqk", wi, np.maximum(
        np.einsum("nqhd,nkd->nqhk", qi, ki), 0.0))
    assert got.dtype == np.float32 and got.shape == (2, 5, 7)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # no negative zero leaves it: a sort would put it under +0.0
    z = att.index_scores(jnp.ones((1, 1, 1, 2)), -jnp.ones((1, 3, 2)),
                         -jnp.ones((1, 1, 1)))
    assert not np.signbit(np.asarray(z)).any()


# ---------------------------------------------------------------------------
# the op against its dense self
# ---------------------------------------------------------------------------
ROPE = {"rope_theta": 1e4}


def _op(sparse, qk_norm=1e-6, n=2, s=24, seed=5, rope=ROPE):
    x = Tensor(shape=(n, s, 32), dtype="float32", name="x")
    op = att.MultiHeadAttention(
        "attention_0", x, x, x, 32, 4, causal=True, use_bias=False,
        num_kv_heads=2, head_dim=8, rope=rope, qk_norm=qk_norm,
        sparse=sparse)
    rng = np.random.default_rng(seed)
    params = {w.name: jnp.asarray(
        1.0 + 0.1 * rng.normal(size=w.shape) if w.name.endswith("norm")
        else 0.3 * rng.normal(size=w.shape), jnp.float32)
        for w in op.weights}
    return op, params


CTX = OpContext(training=False, compute_dtype="float32", mesh=None)


def test_qk_norm_alone_norms_each_head_before_the_rotation():
    """``qk_norm`` without ``sparse``: two more weights, and ``forward``
    equals the plain op fed projections whose heads were normed by hand."""
    op, params = _op(None)
    plain, _ = _op(None, qk_norm=None, rope=None)
    assert {w.name.split("/")[1] for w in op.weights} - {
        w.name.split("/")[1] for w in plain.weights} == {"q_norm", "k_norm"}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)),
                    jnp.float32)
    got = op.forward(params, [x], CTX)[0]
    q, k, v = plain._qkv(params, x, x, x, CTX, None)

    def norm(t, g):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                 + 1e-6) * g

    pos = jnp.arange(24)
    q = att.apply_rope(norm(q, params["attention_0/q_norm"]), pos, op.rope)
    k = att.apply_rope(norm(k, params["attention_0/k_norm"]), pos, op.rope)
    want = plain._out_proj(params, att._dense_attention(
        q, k, v, True, 8 ** -0.5, 0.0, None), 2, 24, CTX, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    unnormed, _ = _op(None, qk_norm=None)
    assert float(jnp.abs(got - unnormed.forward(params, [x], CTX)[0]).max()
                 ) > 1e-3


def test_forward_under_topk_is_the_dense_op_bit_for_bit():
    """A sequence no longer than ``topk`` cannot leave a key out: the same
    bits as the op without ``sparse=`` (which shares its weights' names)."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 32)),
                    jnp.float32)
    dense, params = _op(None)
    roomy, more = _op(dict(SPARSE, topk=24))
    got = roomy.forward(dict(more, **params), [x], CTX)[0]
    assert (np.asarray(got) == np.asarray(dense.forward(params, [x],
                                                        CTX)[0])).all()
    tight, _ = _op(SPARSE)
    assert float(jnp.abs(tight.forward(dict(more, **params), [x], CTX)[0]
                         - got).max()) > 1e-3


def test_forward_attends_over_the_chosen_keys_only():
    """Past ``topk`` a row's output is softmax attention over exactly the
    ``topk`` positions its own index scores put first."""
    op, params = _op(SPARSE, n=1)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, 32)),
                    jnp.float32)
    pos = jnp.arange(24)
    q, k, v = op._qkv(params, x, x, x, CTX, pos)
    qi, ki, wi = op._index(params, x, pos, CTX)
    scores = np.asarray(att.index_scores(qi, ki, wi))[0]
    rows = []
    for t in range(24):
        order = np.argsort(-scores[t, :t + 1], kind="stable")[:TOPK]
        order = np.sort(order)
        kk, vv = np.asarray(k[0, order]), np.asarray(v[0, order])
        out = []
        for h in range(4):
            sc = np.asarray(q[0, t, h]) @ kk[:, h // 2].T * 8 ** -0.5
            p = np.exp(sc - sc.max())
            out.append((p / p.sum()) @ vv[:, h // 2])
        rows.append(np.concatenate(out))
    want = op._out_proj(params, jnp.asarray(np.stack(rows))[None], 1, 24,
                        CTX, x)
    np.testing.assert_allclose(np.asarray(op.forward(params, [x], CTX)[0]),
                               np.asarray(want), atol=2e-5)
    assert op.flops() > 0 and op.internal_io_bytes() > 0


def test_flops_count_the_indexer_and_a_core_of_topk_keys():
    sparse, _ = _op(SPARSE, n=1, s=24)
    dense, _ = _op(None, n=1, s=24)
    proj = 2 * 24 * (2 * 8 * 32 + 8 * 32 + 2 * 32)      # wiq, wik, wiw
    index = 2 * 24 * 24 * 2 * 8
    core = 2 * 2 * 24 * (24 - TOPK) * 4 * 8                # keys not read
    assert sparse.flops() == dense.flops() + proj + index - core
    assert sparse.internal_io_bytes() == dense.internal_io_bytes() \
        + 8 * 3 * 24 * 24
    roomy, _ = _op(dict(SPARSE, topk=24), n=1, s=24)
    assert roomy.internal_io_bytes() == dense.internal_io_bytes()


# ---------------------------------------------------------------------------
# serving: the forms
# ---------------------------------------------------------------------------
def test_sparse_graph_serves_what_its_forward_computes(sparse_lm):
    """Prefill in chunks of 8 then token steps through the engine's three
    leaves against the graph's own full forward at every served position
    (float32: the same tokens), prompts under ``topk``, across pages and past
    several chunks, two streams at once.  Nothing is refused; ``stats()``
    says what a token costs with the third leaf in it, which core the
    sparse layers got, and what they chose."""
    model = sparse_lm
    rng = np.random.default_rng(44)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (5, 8, 13, 23, 37)]
    eng = GenerationEngine(model, slots=2)
    dec = eng._decoder
    assert dec.pageable and not dec.windowed
    for what in ("prefix reuse", "speculation", "migration"):
        assert dec.refusal(what) is None
    pool = (dec.num_pages, 4)
    for name in ("attention_0", "attention_1"):
        assert dec.layout[name]["shapes"] == {
            "k": pool + (16,), "v": pool + (16,), "ik": pool + (128,)}
        assert dec.layout[name + "/counters"]["kind"] == "counter"
        assert dec.layout[name + "/counters"]["shapes"] == {"counts": (4, 2)}
    assert eng.kv_plan["page_bytes"] == 2 * 4 * (16 + 16 + 128) * 4
    with eng:
        outs = [[int(t) for t in s.result(timeout=300)] for s in
                [eng.submit(p, max_new_tokens=10) for p in prompts]]
        snap = eng.stats()
    for p, out in zip(prompts, outs):
        assert out == reference_decode(model, p, 10, SEQ)
    assert snap["decode_attention"] == {
        "paged": 0, "gathered": 2,
        "sparse": {"rows": 0, "paged": 0, "gathered": 2}}
    assert snap["chunk_attention"] == {"sparse": {
        "mask": 6, "gather": 0, "loop": 0, "dense": 0}}
    assert snap["kv_pages"]["full"]["bytes_per_token"] == {
        "attention_0": 4 * 160, "attention_1": 4 * 160}
    for name in ("attention_0", "attention_1"):
        got = snap["sparse_attention"][name]
        assert got["topk"] == TOPK
        # every prompt row and every token step but each stream's last
        assert got["queries"] >= sum(map(len, prompts)) + 5 * 9
        assert 0 < got["dense_queries"] < got["queries"]
        assert got["chosen_mean"] <= TOPK < got["live_mean"]


def _chunk_step(op, params, state, x, start, length, table, pages):
    out, new = op.serve_step(params, [x], state, ServeStep(
        "chunk", table, start=jnp.int32(start), length=jnp.int32(length),
        slot=jnp.int32(0), no_page=pages), CTX)
    return out[0], new


def _pools(op, pages, page):
    return {"k": jnp.zeros((pages, page, op.kv_dim)),
            "v": jnp.zeros((pages, page, op.kv_dim)),
            "ik": jnp.zeros((pages, page, op.index_width)),
            "counts": jnp.zeros((4, 2), jnp.int32)}


@pytest.mark.parametrize("topk", [8, 100])
def test_the_loop_over_key_blocks_is_the_masked_chunk(topk, monkeypatch):
    """A table past one key block takes the loop (scores, threshold and core
    a block at a time); the same op told that a block is longer than the
    table takes the mask over the whole view.  640 positions prefilled in
    chunks of 64 (pad rows in the last): the same outputs to rounding, the
    same rows in the three leaves, the same counts."""
    pages, page, L = 44, 16, 640
    sparse = dict(SPARSE, topk=topk)
    op, params = _op(sparse, n=1, s=64)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(10, 1, 64, 32)),
                    jnp.float32)
    table = jnp.arange(2, 42, dtype=jnp.int32)
    outs = {}
    for form, block in (("loop", 512), ("mask", 1024)):
        monkeypatch.setattr(att, "_KEY_BLOCK", block)
        state = _pools(op, pages, page)
        got = []
        for c in range(10):
            length = 64 if c < 9 else 50
            out, state = _chunk_step(op, params, state, x[c], 64 * c, length,
                                     table, pages)
            got.append(np.asarray(out[0, :length]))
        assert op.chunk_core == {64: form}
        outs[form] = (np.concatenate(got), state)
    a, b = outs["loop"], outs["mask"]
    np.testing.assert_allclose(a[0], b[0], atol=3e-5)
    for leaf in ("k", "v", "ik"):
        assert (np.asarray(a[1][leaf]) == np.asarray(b[1][leaf])).all()
    counts = op.selection_stats(a[1]["counts"])
    assert counts == op.selection_stats(b[1]["counts"])
    rows = 9 * 64 + 50
    assert counts["queries"] == rows
    assert counts["dense_queries"] == min(topk, rows)
    assert counts["live_mean"] == pytest.approx((rows + 1) / 2)
    assert counts["chosen_mean"] == pytest.approx(sum(
        min(t + 1, topk) for t in range(rows)) / rows)


def test_a_token_step_that_copies_its_chosen_rows_is_the_masked_view(
        monkeypatch):
    """The token step's two forms on one state: ``rows`` (what a TPU takes:
    the choice in the kernel, the chosen rows gathered out of the pools)
    against ``gathered`` (the whole view under a mask), slots at positions under and
    past ``topk``, one not decoding."""
    pages, page, slots = 40, 4, 4
    op, params = _op(SPARSE, n=slots, s=1)
    rng = np.random.default_rng(8)
    state = {"k": jnp.asarray(rng.normal(size=(pages, page, 16)), jnp.float32),
             "v": jnp.asarray(rng.normal(size=(pages, page, 16)), jnp.float32),
             "ik": jnp.asarray(rng.normal(size=(pages, page, 128)),
                               jnp.float32).at[..., 8:].set(0),
             "counts": jnp.zeros((4, 2), jnp.int32)}
    table = jnp.asarray(rng.permutation(pages).reshape(slots, 10), jnp.int32)
    pos = jnp.asarray([3, 17, 30, 39], jnp.int32)
    wp = jnp.take_along_axis(table, (pos // page)[:, None], 1)[:, 0]
    wp = wp.at[2].set(pages)        # slot 2 is not decoding
    x = jnp.asarray(rng.normal(size=(slots, 1, 32)), jnp.float32)
    where = ServeStep("token", table, pos=pos, write_pages=wp,
                      write_rows=pos % page, no_page=pages)
    got = {}
    for form in ("gathered", "rows"):
        monkeypatch.setattr(op, "_decode_core", lambda pool, ctx, f=form: (
            "paged" if f == "rows" else "gathered"))
        out, new = op.serve_step(params, [x], state, where, CTX)
        assert op.decode_core == form
        got[form] = (np.asarray(out[0]), op.selection_stats(new["counts"]))
    live = [0, 1, 3]
    np.testing.assert_allclose(got["rows"][0][live], got["gathered"][0][live],
                               atol=2e-5)
    assert got["rows"][1] == got["gathered"][1] == {
        "topk": TOPK, "queries": 3, "dense_queries": 1,
        "chosen_mean": (4 + 8 + 8) / 3, "live_mean": (4 + 18 + 40) / 3}


def _steer_to_the_kernel(monkeypatch):
    """What one TPU answers, on the CPU: the token step copies its chosen
    rows and chooses them in ``ops/paged_index_kernel.py`` (the
    interpreter's here; pages of 4 float32 rows are what the tiny graphs
    have, not what a chip's copy takes)."""
    monkeypatch.setattr(att.MultiHeadAttention, "_decode_core",
                        lambda self, pool, ctx: "paged")


@pytest.mark.parametrize("positions,idle", [
    ((3, 17, 30, 39), (2,)),        # under and past topk, one not decoding
    ((39, 38, 37, 36), ()),         # every slot at the table's end
    ((0, 5, 7, 8), (0, 3)),         # nothing to leave out but in one slot
    ((12, 12, 12, 12), (0, 1, 2)),  # one slot decodes
])
def test_a_token_step_that_chooses_in_the_kernel_is_the_masked_view(
        monkeypatch, positions, idle):
    """The token step's two forms on one state, at pages that lie out of
    order: ``rows`` (the choice made by the kernel: scores against the
    pages of ``ik`` where they lie, the threshold found on the slot's score
    block, the list by rank) against ``gathered`` (the whole view under a
    mask): the same outputs for the decoding slots, the same rows in the
    three leaves, the same counts."""
    pages, page, slots = 40, 4, 4
    op, params = _op(SPARSE, n=slots, s=1)
    rng = np.random.default_rng(sum(positions))
    state = {"k": jnp.asarray(rng.normal(size=(pages, page, 16)), jnp.float32),
             "v": jnp.asarray(rng.normal(size=(pages, page, 16)), jnp.float32),
             "ik": jnp.asarray(rng.normal(size=(pages, page, 128)),
                               jnp.float32).at[..., 8:].set(0),
             "counts": jnp.zeros((4, 2), jnp.int32)}
    table = jnp.asarray(rng.permutation(pages).reshape(slots, 10), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    wp = jnp.take_along_axis(table, (pos // page)[:, None], 1)[:, 0]
    wp = wp.at[jnp.asarray(idle, jnp.int32)].set(pages)
    x = jnp.asarray(rng.normal(size=(slots, 1, 32)), jnp.float32)
    where = ServeStep("token", table, pos=pos, write_pages=wp,
                      write_rows=pos % page, no_page=pages)
    got = {}
    for form, core in (("gathered", "gathered"), ("rows", "paged")):
        monkeypatch.setattr(op, "_decode_core", lambda pool, ctx, c=core: c)
        out, new = op.serve_step(params, [x], state, where, CTX)
        assert op.decode_core == form
        got[form] = (np.asarray(out[0]), new)
    live = [s for s in range(slots) if s not in idle]
    np.testing.assert_allclose(got["rows"][0][live],
                               got["gathered"][0][live], atol=2e-5)
    for leaf in ("k", "v", "ik"):
        assert (np.asarray(got["rows"][1][leaf])
                == np.asarray(got["gathered"][1][leaf])).all()
    assert op.selection_stats(got["rows"][1]["counts"]) \
        == op.selection_stats(got["gathered"][1]["counts"])
    counts = op.selection_stats(got["rows"][1]["counts"])
    assert counts["queries"] == len(live)
    assert counts["chosen_mean"] == pytest.approx(
        sum(min(positions[s] + 1, TOPK) for s in live) / len(live))


def test_the_sparse_graph_serves_its_tokens_through_the_kernel(monkeypatch):
    """The graph's engine with every token step steered to the kernel: the
    tokens the graph's own forward gives, at histories under and past
    ``topk`` and across pages, two streams at once; ``stats()`` says that
    the rows were copied and that ``topk`` positions at most were chosen;
    the chunk programs are not touched."""
    _steer_to_the_kernel(monkeypatch)
    model = _build(seed=11)
    rng = np.random.default_rng(47)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (5, 13, 23, 37)]
    outs, snap = _serve(model, prompts)
    for p, out in zip(prompts, outs):
        assert out == reference_decode(model, p, 10, SEQ)
    assert snap["decode_attention"] == {
        "paged": 0, "gathered": 0,
        "sparse": {"rows": 2, "paged": 0, "gathered": 0}}
    assert snap["chunk_attention"]["sparse"]["mask"] > 0
    for name in ("attention_0", "attention_1"):
        got = snap["sparse_attention"][name]
        assert got["chosen_mean"] <= TOPK < got["live_mean"]


@pytest.mark.parametrize("pages_per_slot,core,form", [
    (TOPK - 1, "paged", "paged"), (TOPK, "paged", "paged"),
    (TOPK + 1, "paged", "rows"), (4 * TOPK, "paged", "rows"),
    (TOPK, "gathered", "gathered"), (TOPK + 1, "gathered", "gathered")])
def test_the_token_form_is_read_off_the_tables_shape(monkeypatch,
                                                     pages_per_slot, core,
                                                     form):
    """Where the in-place read applies, a table of no more pages than the
    op chooses rows reads its pages under the set as a mask and a longer one
    copies the chosen rows; elsewhere the masked view.  Shapes at trace
    time, nothing else."""
    op, _ = _op(SPARSE, n=3, s=1)
    monkeypatch.setattr(op, "_decode_core", lambda pool, ctx: core)
    table = jax.ShapeDtypeStruct((3, pages_per_slot), jnp.int32)
    pool = jax.ShapeDtypeStruct((3 * pages_per_slot, 4, 16), jnp.float32)
    assert op._token_form(pool, table, CTX) == form


@pytest.mark.parametrize("ties", [False, True], ids=["", "ties"])
@pytest.mark.parametrize("positions,idle", [
    ((3, 17, 30, 39), (2,)),        # under and past topk, one not decoding
    ((39, 38, 37, 36), ()),         # every slot at the table's end
    ((0, 5, 11, 12), (0, 3)),       # nothing to leave out but in one slot
    ((20, 20, 20, 20), (0, 1, 2)),  # one slot decodes
])
def test_a_token_step_that_reads_its_pages_under_the_set_is_the_masked_view(
        monkeypatch, positions, idle, ties):
    """The token step's three forms on one state, at pages that lie out of
    order, an op that keeps 12 of a table of 10 pages of 4: ``paged`` (the
    choice by the kernel, then the paged decode kernel over every live page
    under the set as a mask: no list, nothing gathered) against ``rows``
    (the set as a list, its rows gathered) and ``gathered`` (the whole view
    under a mask): the same outputs for the decoding slots, the same rows in
    the three leaves, the same counts.  With ``ties`` every cached position
    holds one of THREE indexer keys, so a third of a slot's positions score
    the same to the bit, many tie AT the threshold and the lower ones must be
    the ones kept."""
    pages, page, slots, topk = 40, 4, 4, 12
    op, params = _op(dict(SPARSE, topk=topk), n=slots, s=1)
    rng = np.random.default_rng(sum(positions))
    ik = rng.normal(size=(pages, page, 128))
    if ties:
        ik = rng.normal(size=(3, 128))[rng.integers(0, 3, (pages, page))]
    state = {"k": jnp.asarray(rng.normal(size=(pages, page, 16)), jnp.float32),
             "v": jnp.asarray(rng.normal(size=(pages, page, 16)), jnp.float32),
             "ik": jnp.asarray(ik, jnp.float32).at[..., 8:].set(0),
             "counts": jnp.zeros((4, 2), jnp.int32)}
    table = jnp.asarray(rng.permutation(pages).reshape(slots, 10), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    wp = jnp.take_along_axis(table, (pos // page)[:, None], 1)[:, 0]
    wp = wp.at[jnp.asarray(idle, jnp.int32)].set(pages)
    x = jnp.asarray(rng.normal(size=(slots, 1, 32)), jnp.float32)
    where = ServeStep("token", table, pos=pos, write_pages=wp,
                      write_rows=pos % page, no_page=pages)
    monkeypatch.setattr(op, "_decode_core", lambda pool, ctx: "paged")
    got = {}
    for form in ("paged", "gathered", "rows"):
        if form != "paged":     # held to the other forms: the rule says paged
            monkeypatch.setattr(op, "_token_form",
                                lambda pool, table, ctx, f=form: f)
        out, new = op.serve_step(params, [x], state, where, CTX)
        assert op.decode_core == form
        got[form] = (np.asarray(out[0]), new)
    live = [s for s in range(slots) if s not in idle]
    for other in ("gathered", "rows"):
        np.testing.assert_allclose(got["paged"][0][live],
                                   got[other][0][live], atol=2e-5)
        for leaf in ("k", "v", "ik"):
            assert (np.asarray(got["paged"][1][leaf])
                    == np.asarray(got[other][1][leaf])).all()
        assert op.selection_stats(got["paged"][1]["counts"]) \
            == op.selection_stats(got[other][1]["counts"])
    counts = op.selection_stats(got["paged"][1]["counts"])
    assert counts["queries"] == len(live)
    assert counts["chosen_mean"] == pytest.approx(
        sum(min(positions[s] + 1, topk) for s in live) / len(live))


def test_the_sparse_graph_serves_the_same_tokens_from_pages_and_from_rows(
        monkeypatch):
    """A graph that keeps 16 of a table of 12 pages, every token step
    steered to the kernels: by the rule it reads its pages under the set as
    a mask (``"paged"``: counted under ``"sparse"`` and, reading the pool in
    place, in the graph's ``"paged"`` total); held to ``"rows"`` it serves
    the same tokens, which are the ones the graph's own forward gives, at
    histories under and past ``topk``, two streams at once."""
    _steer_to_the_kernel(monkeypatch)
    model = _build(dict(SPARSE, topk=16), seed=11)
    rng = np.random.default_rng(48)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (5, 13, 23, 37)]
    paged, snap = _serve(model, prompts)
    assert snap["decode_attention"] == {
        "paged": 2, "gathered": 0,
        "sparse": {"rows": 0, "paged": 2, "gathered": 0}}
    for name in ("attention_0", "attention_1"):
        got = snap["sparse_attention"][name]
        assert got["chosen_mean"] <= 16 < got["live_mean"]
    monkeypatch.setattr(att.MultiHeadAttention, "_token_form",
                        lambda self, pool, table, ctx: "rows")
    # (a graph of its own: a model keeps the programs it traced)
    rows, snap = _serve(_build(dict(SPARSE, topk=16), seed=11), prompts)
    assert snap["decode_attention"]["sparse"] == {
        "rows": 2, "paged": 0, "gathered": 0}
    assert paged == rows
    for p, out in zip(prompts, paged):
        assert out == reference_decode(model, p, 10, SEQ)


def test_serving_under_topk_is_the_dense_graph_bit_for_bit():
    """Prompts and outputs that stay under ``topk`` positions, on a table
    LONGER than ``topk`` (so the indexer runs and chooses everything): the
    logits a chunk and a token step produce are the bits of the same graph
    without ``sparse=`` given the same weights."""
    sparse, dense = _build(dict(SPARSE, topk=16), seed=3), _build(None,
                                                                   seed=3)
    shared = {k: v for k, v in sparse._params.items() if k in dense._params}
    dense._params = dict(dense._params, **shared)
    prompt = np.random.default_rng(9).integers(1, VOCAB, (1, 8)).astype(
        np.int32)
    logits = {}
    for name, model in (("sparse", sparse), ("dense", dense)):
        dec = GraphDecoder(model, 2, SEQ, prefill_chunk=CHUNK)
        caches = dec.init_cache()
        table = jnp.arange(dec.pages_per_slot, dtype=jnp.int32)
        chunk, caches = jax.jit(lambda p, c, t: dec._walk(p, c, t, ServeStep(
            "chunk", table, start=jnp.int32(0), length=jnp.int32(8),
            slot=jnp.int32(0), no_page=dec.num_pages)))(
                model._params, caches, jnp.asarray(prompt))
        tables = jnp.stack([table, table + dec.pages_per_slot])
        pos = jnp.asarray([8, 0], jnp.int32)
        step, _ = jax.jit(lambda p, c, t: dec._walk_decode(
            p, c, t, pos, tables, jnp.asarray([2, dec.num_pages], jnp.int32),
            pos % 4))(model._params, caches, jnp.asarray([5, 0], jnp.int32))
        logits[name] = (np.asarray(chunk), np.asarray(step[0]))
    assert sparse.layers[2].chunk_core == {8: "mask"}
    for a, b in zip(logits["sparse"], logits["dense"]):
        assert (a == b).all()


def test_the_third_leaf_is_lent_rolled_back_and_shipped(sparse_lm):
    """The three things a windowed entry refuses, on the sparse graph: a
    prompt that REUSES another's first pages (its ``ik`` rows with them), a
    divergent draft whose windows are partly REJECTED (each window row its
    own chosen set), and a stream that prefills on one engine and decodes on
    another each serve the tokens the graph's own forward gives (float32)."""
    from tests.serving_fixtures import build_disagg

    model = sparse_lm
    rng = np.random.default_rng(45)
    first = rng.integers(1, VOCAB, 30).astype(np.int32)
    second = np.concatenate([first[:25], rng.integers(1, VOCAB, 6)]).astype(
        np.int32)
    refs = [reference_decode(model, p, 10, SEQ) for p in (first, second)]
    outs, snap = _serve(model, (first, second))
    assert outs == refs and snap["prefix_hit_tokens"] == 24
    outs, snap = _serve(model, (first, second), draft_model=_build(seed=7),
                        spec_gamma=3)
    assert outs == refs and snap["spec"] == "on"
    assert 0 < snap["spec_proposed_tokens"] > snap["spec_accepted_tokens"]
    with ff.fflogger.silenced("serve"):
        router, fleets, _ = build_disagg(model, 2, SEQ, CHUNK,
                                         prefix_cache="off", pf_pace_s=0.0)
        try:
            outs = [[int(t) for t in router.submit(
                "lm", p, max_new_tokens=10).result(timeout=300)]
                for p in (first, second)]
            stats = router.stats()
        finally:
            router.stop()
            for f in fleets:
                f.stop()
    assert outs == refs
    assert stats["migrations"] == 2 and stats["migrated_bytes"] > 0


def test_a_verify_window_row_is_the_sequential_token_step(sparse_lm):
    """Greedy speculation with the graph as its OWN draft accepts every
    proposal: window row t chose what the token step at that position
    chooses, bit for bit."""
    model = sparse_lm
    prompts = [np.random.default_rng(46).integers(1, VOCAB, n).astype(
        np.int32) for n in (11, 26)]
    plain, _ = _serve(model, prompts, new=12)
    outs, snap = _serve(model, prompts, new=12, draft_model=_build(),
                        spec_gamma=4)
    assert outs == plain
    assert snap["spec_accepted_tokens"] == snap["spec_proposed_tokens"] > 0


def test_the_owner_tables_tell_the_three_parts_apart(sparse_lm):
    eng = GenerationEngine(sparse_lm, slots=2)
    dec = eng._decoder
    dec.decode_fn()
    dec.prefill_fn(8)
    tables = eng.program_op_tables()
    for name in ("jit_decode", "jit_prefill_8"):
        owners = set(tables[name].values())
        for part in ("dsa_index", "dsa_select", "dsa_core"):
            assert ("attention_0", part) in owners, (name, part)
        assert ("attention_0", None) in owners      # q/k/v, the output
        assert any(p == "moe_experts" for _, p in owners)


def _program_digests(model):
    """sha256 of the lowered text of the model's token step and its
    8-token chunk program."""
    dec = GraphDecoder(model, 2, model.input_tensors[0].shape[1])
    dec.decode_fn()
    dec.prefill_fn(8)
    return {name: hashlib.sha256(fn.lower(*args).as_text().encode()
                                 ).hexdigest()
            for _, name, fn, args in dec._program_specs()}


def test_the_latent_graphs_programs_are_the_parents():
    """The third graph that was there (latent attention, PR 41) lowers its
    token step and a chunk program to the text pinned here (sha256 under
    this suite's ``conftest``); ``test_generation.py`` pins the other two.
    MOVED ON PURPOSE by PR 45: the graph's sparse layer holds 8 of its 16
    experts, and an op that holds fewer experts than its router scores now
    walks its OWN pairs in blocks under a loop (``MoE._experts``) where it
    gathered, masked and scatter-added every pair, so both programs hold a
    ``while`` they did not (before PR 45: ``cc389ffe..08d9be`` and
    ``8cbf019c..69d974``); the tokens they serve are the parent's
    (:func:`test_a_tiny_pangu_engine_serves_the_parents_tokens`)."""
    assert _program_digests(_build_latent_lm(weights=False)) == {
        "jit_prefill_8": LATENT_PINS[0], "jit_decode": LATENT_PINS[1]}


def test_the_sparse_graphs_programs_are_the_parents():
    """The fourth graph (a learned selection of keys, PR 44; its experts
    all held) lowers its token step and a chunk program to the text it
    lowered to before PR 45, the first to edit code they run (sha256 read
    on the parent commit under this suite's ``conftest``).  A PR that
    changes one of these programs on purpose says which and why, and moves
    the pin."""
    assert _program_digests(_build(weights=False)) == {
        "jit_prefill_8": SPARSE_PINS[0], "jit_decode": SPARSE_PINS[1]}


def test_a_tiny_pangu_engine_serves_the_parents_tokens():
    """The tiny latent graph (16 experts, 8 held) serves, greedy, the
    tokens it served before its dispatch walked its own pairs in blocks
    (read on PR 45's parent commit, float32 on the CPU), and
    ``stats()["moe"]`` says which form each program of the sparse layer
    took and what the blocks ran: 8 of 16 held, so a block is all of a
    step's pairs and no step needs a second."""
    model = _build_latent_lm()
    rng = np.random.default_rng(45)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (5, 8, 13, 23, 37)]
    with ff.fflogger.silenced("serve"):
        with GenerationEngine(model, slots=2) as eng:
            outs = [[int(t) for t in s.result(timeout=300)] for s in
                    [eng.submit(p, max_new_tokens=16) for p in prompts]]
            moe = eng.stats()["moe"]["moe_1"]
    assert outs == PARENTS_TOKENS
    assert moe["dispatch"] == {
        "chunk:2": {"rows": 8, "of": 8}, "chunk:4": {"rows": 16, "of": 16},
        "chunk:8": {"rows": 32, "of": 32}, "token:2": {"rows": 8, "of": 8}}
    assert 0.3 < moe["own_share"] < 0.7         # 8 of 16 experts are here
    assert 0.9 < moe["blocks_per_step"] <= 1.0
    assert moe["past_one_block_share"] == 0.0


def test_an_op_that_holds_every_expert_reports_the_whole_dispatch(sparse_lm):
    """The sparse graph's ops hold all 8 of their experts: every serving
    program of every one of them took the one pass over all pairs, and
    nothing is said of blocks."""
    _, snap = _serve(sparse_lm, [np.arange(1, 12, dtype=np.int32)], new=4)
    ops = {n: m for n, m in snap["moe"].items() if n != "grouped_product"}
    assert sorted(ops) == ["moe_0", "moe_1"]
    for m in ops.values():
        # (the module's model: a test before this one may have traced
        # verify windows too)
        assert {"chunk:2", "chunk:4", "chunk:8", "token:2"} <= set(
            m["dispatch"])
        assert set(m["dispatch"].values()) == {"whole"}
        assert "own_share" not in m and "blocks_per_step" not in m


# moved by PR 45 (the docstring above)
LATENT_PINS = (
    "e78eb9567407e727755842fb2e71b834f3d5c96b789542c1f3bc1c69b74232e8",
    "197bb79058ad10655edd304360c3c948544c18bb0e4e8dbb6b41e100bf405a59")
# read on PR 45's parent commit
SPARSE_PINS = (
    "60c5b2e28a2bc9fc00e6e120b80d6cfe416c19e34fe4816645092b77848a9347",
    "88f8f8e7fdfa723e37f7e068172d2656c35e159c14a9a310b8427c6a1f4c5200")
PARENTS_TOKENS = [
    [29, 19, 19, 19, 19, 27, 27, 27, 9, 27, 57, 27, 9, 27, 30, 9],
    [15, 54, 51, 48, 48, 48, 11, 5, 9, 11, 9, 11, 9, 60, 60, 60],
    [6, 33, 11, 48, 48, 48, 9, 9, 9, 11, 52, 6, 11, 52, 60, 60],
    [7, 31, 9, 31, 7, 52, 18, 7, 52, 9, 31, 1, 11, 18, 18, 35],
    [49, 52, 4, 11, 27, 4, 49, 52, 4, 49, 52, 44, 42, 42, 42, 42]]
