"""Token-generation subsystem tests (ISSUE 11, docs/serving.md "Token
generation").

The correctness anchor is the decode==forward parity suite: the
KV-cached single-token decode must reproduce the full-sequence forward
BIT-IDENTICALLY on CPU at every prefix length, for both the attention
op and the LSTM cell (prefill == forward by shared code; decode by the
q-padding / 2-step-scan kernel contracts in ops/attention.py and
ops/rnn.py).  On top of that: the GenerationEngine's token streams must
equal the replicated predict-style reference decode token-for-token —
on {n:1} AND on a strategy-sharded {n:2, c:2} mesh — plus continuous
batching, streaming, cancellation, admission reuse, KV-cache memory
accounting and the FF_FAULT generation kinds.
"""

import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flexflow_tpu as ff
from flexflow_tpu import faults
from flexflow_tpu.fflogger import capture_events
from flexflow_tpu.op import Op, OpContext, OpType, ServeStep
from flexflow_tpu.ops.attention import MultiHeadAttention, PositionEmbedding
from flexflow_tpu.ops.rnn import LSTM
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.serving.errors import (DeadlineExceeded,
                                         GenerationCancelled,
                                         OverloadError, SheddedError)
from flexflow_tpu.serving.generation import (GenerationEngine,
                                             GraphDecoder, SamplingParams)
from flexflow_tpu.tensor import Tensor

VOCAB = 61
SEQ = 32


# ---------------------------------------------------------------------
# op-level parity: decode-with-cache == full-sequence forward, bitwise
# ---------------------------------------------------------------------
def _op_params(op, key, offset=0):
    params = {}
    for i, w in enumerate(op.weights):
        params[w.name] = w.initializer(jax.random.fold_in(key, offset + i),
                                       w.shape, jnp.float32)
    return params


def _ctx():
    return OpContext(training=False, compute_dtype="float32", mesh=None)


def _state(op, slots, num_pages=0, page_size=0):
    """Zeroed f32 leaves of what ``op`` declares it keeps."""
    ent = op.serve_state(slots, num_pages, page_size, None)
    return {leaf: jnp.zeros(shape, jnp.float32)
            for leaf, shape in ent["shapes"].items()}


def _stepper(op, kind):
    """``op.serve_step`` for one kind of step, jitted as the decoder's
    programs jit it: ``run(params, x, state, **index arrays)``."""
    ctx = _ctx()

    def run(params, x, state, **where):
        return op.serve_step(params, [x], state,
                             ServeStep(kind, **where), ctx)

    return jax.jit(run)


def _chunk(table_row, slot, start, length):
    """The index arrays of one slot's prompt chunk."""
    return {"table": None if table_row is None else jnp.asarray(table_row),
            "slot": jnp.int32(slot), "start": jnp.int32(start),
            "length": jnp.int32(length)}


def test_attention_decode_matches_forward_every_prefix():
    """The correctness anchor, on what serves: the paged step through
    the serving contract — a scrambled page table, a folded pool, one
    position of every slot per call, as ``jit_decode`` runs it —
    reproduces the causal forward's row at EVERY prefix length, to
    float32 tolerance (ROADMAP D6: the contract is the mathematics, not
    one XLA:CPU build's accumulation order — JAX 0.9.0 drifts 1 ulp
    here); and so does each row of a prompt chunk."""
    n, S, D, H, page = 2, 16, 32, 4, 4
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, S, D)).astype(np.float32)
    t_in = Tensor((n, S, D), "float32", "x")
    op = MultiHeadAttention("attn", t_in, t_in, t_in, D, H, causal=True)
    params = _op_params(op, jax.random.PRNGKey(0))
    ctx = _ctx()
    # 8 pages of 4 rows, no slot's pages in order or next to each other
    table = np.array([[5, 2, 7, 0], [3, 6, 1, 4]], np.int32)
    no_page = table.size
    empty = _state(op, n, num_pages=no_page, page_size=page)
    assert empty["k"].shape == (no_page, page, D)      # folded rows

    full = jax.jit(lambda p, x: op.forward(p, [x], ctx)[0])(params, x)
    chunk, token = _stepper(op, "chunk"), _stepper(op, "token")
    # prefill: one chunk a slot; its rows are the forward's rows
    filled = empty
    for i in range(n):
        (out,), filled = chunk(params, x[i:i + 1], filled,
                               **_chunk(table[i], i, 0, S))
        np.testing.assert_allclose(np.asarray(out)[0],
                                   np.asarray(full)[i],
                                   rtol=1e-5, atol=1e-6)
    khost = np.asarray(filled["k"])

    state = empty
    for t in range(S):
        where = {"table": jnp.asarray(table),
                 "pos": jnp.full((n,), t, jnp.int32),
                 "write_pages": jnp.asarray(table[:, t // page]),
                 "write_rows": jnp.full((n,), t % page, jnp.int32)}
        (out,), state = token(params, x[:, t:t + 1], state, **where)
        got, want = np.asarray(out)[:, 0], np.asarray(full)[:, t]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=f"t={t}")
        # the step wrote this position's K row where the table says —
        # the row the prefill wrote there
        np.testing.assert_array_equal(
            np.asarray(state["k"])[table[:, t // page], t % page],
            khost[table[:, t // page], t % page])
    # a slot that is not decoding writes nothing: the sentinel drops it
    where["write_pages"] = jnp.full((n,), no_page, jnp.int32)
    _, after = token(params, x[:, :1], state, **where)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(after[leaf]),
                                      np.asarray(state[leaf]))


def test_lstm_decode_matches_forward_every_prefix():
    """The RNN cell's token step (state carry in a 2-step scan — see
    ops/rnn.py for why the scan matters) matches the scanned forward
    bit-for-bit, both step-by-step and seeded at mid-sequence by a
    prompt chunk, which writes its last real position's carry."""
    n, S, D, H = 2, 16, 24, 8
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, S, D)).astype(np.float32)
    t_in = Tensor((n, S, D), "float32", "x")
    op = LSTM("lstm", t_in, H)
    params = _op_params(op, jax.random.PRNGKey(1))
    ctx = _ctx()
    exact = jax.default_backend() == "cpu"

    def same(got, want, **kw):
        if exact:
            np.testing.assert_array_equal(got, want, **kw)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    forward = jax.jit(lambda p, x: op.forward(p, [x], ctx)[0])
    fseq = np.asarray(forward(params, x))
    chunk, token = _stepper(op, "chunk"), _stepper(op, "token")
    state = _state(op, n)
    assert state["h"].shape == state["c"].shape == (n, H)
    for t in range(S):
        (o, _, _), state = token(params, x[:, t:t + 1], state, table=None)
        same(np.asarray(o)[:, 0], fseq[:, t], err_msg=f"t={t}")
    # a prompt chunk IS the forward of that one row, and leaves the
    # carry from which the next position continues the trajectory
    for t0 in (5, 11):
        state = _state(op, n)
        for i in range(n):
            (o, _, _), state = chunk(params, x[i:i + 1], state,
                                     **_chunk(None, i, 0, t0))
            same(np.asarray(o)[0, :t0],
                 np.asarray(forward(params, x[i:i + 1]))[0, :t0])
        (o, _, _), _ = token(params, x[:, t0:t0 + 1], state, table=None)
        same(np.asarray(o)[:, 0], fseq[:, t0])
    with pytest.raises(ValueError, match="lstm.*roll back"):
        op.serve_step(params, [x], state, ServeStep("window", None), ctx)


def test_position_embedding_decode_matches_forward():
    n, S, D = 2, 12, 16
    rng = np.random.default_rng(2)
    x = rng.standard_normal((n, S, D)).astype(np.float32)
    t_in = Tensor((n, S, D), "float32", "x")
    op = PositionEmbedding("pe", t_in)
    params = _op_params(op, jax.random.PRNGKey(2))
    ctx = _ctx()
    full = np.asarray(
        jax.jit(lambda p, x: op.forward(p, [x], ctx)[0])(params, x))
    token = _stepper(op, "token")
    for t in range(S):
        (out,), _ = token(params, x[:, t:t + 1], None, table=None,
                          pos=jnp.full((n,), t, jnp.int32))
        np.testing.assert_array_equal(np.asarray(out)[:, 0], full[:, t])
    # a window of every slot and a prompt chunk at an offset read the
    # same table rows
    (out,), _ = _stepper(op, "window")(
        params, x[:, 3:7], None, table=None,
        pos=jnp.full((n,), 3, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), full[:, 3:7])
    (out,), state = _stepper(op, "chunk")(params, x[1:, 4:8], None,
                                          **_chunk(None, 1, 4, 3))
    np.testing.assert_array_equal(np.asarray(out), full[1:, 4:8])
    assert state is None
    assert op.serve_state(n, 8, 4, None) is None       # keeps nothing


# ---------------------------------------------------------------------
# engine-level: GenerationEngine == replicated predict-style decode
# ---------------------------------------------------------------------
def _build_lm(seed=0, mesh_shape=None, slots=2, num_layers=2, d_model=32,
              d_ff=64, compute_dtype="float32", weights=True):
    from flexflow_tpu.models import build_transformer_lm
    cfg = ff.FFConfig(batch_size=4, compute_dtype=compute_dtype, seed=seed)
    cfg.serve_gen_slots = slots
    model = build_transformer_lm(cfg, num_layers=num_layers,
                                 d_model=d_model, num_heads=2, d_ff=d_ff,
                                 seq_len=SEQ, vocab_size=VOCAB)[0]
    model.compile(ff.SGDOptimizer(lr=0.01),
                  mesh=MachineMesh(mesh_shape or {"n": 1}))
    if weights:
        model.init_layers(seed=seed)
    return model


def reference_decode(model, prompt, max_new, max_seq=SEQ):
    """Replicated predict-style decode: full forward over the padded
    prompt at every step, argmax at the last position."""
    toks = [int(t) for t in prompt]
    for _ in range(max_new):
        padded = np.zeros((1, max_seq), np.int32)
        padded[0, :len(toks)] = toks
        probs = model.predict([padded], batch_size=2)
        toks.append(int(np.argmax(probs[0, len(toks) - 1])))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def lm():
    return _build_lm()


@pytest.fixture(scope="module")
def lm_lane_dense():
    """One layer whose folded K/V rows fill whole lanes: 2 heads x 64 =
    128; d_ff 192 so that no weight has a pool's element count."""
    return _build_lm(slots=4, num_layers=1, d_model=128, d_ff=192)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(1, VOCAB, int(rng.integers(2, 9)))
            .astype(np.int32) for _ in range(6)]


def test_engine_matches_reference_decode(lm, prompts):
    """Acceptance pin, replicated half: engine streams == the
    replicated predict-style reference, token for token, with tokens
    retiring incrementally through the stream iterator."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=6)
    with eng:
        streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
        iterated = [list(s) for s in streams]      # streaming surface
        finals = [list(int(t) for t in s.result(timeout=120))
                  for s in streams]
    refs = [reference_decode(lm, p, 6) for p in prompts]
    assert finals == refs
    assert iterated == finals  # the iterator saw exactly the tokens
    snap = eng.stats()
    assert snap["requests"] == len(prompts)
    assert snap["tokens"] == 6 * len(prompts)
    assert snap["prefills"] == len(prompts)
    assert snap["kv_cache_bytes"] > 0


def test_engine_eos_stops_stream(lm, prompts):
    ref = reference_decode(lm, prompts[0], 6)
    eos = ref[2]
    eng = GenerationEngine(lm, slots=2, eos_id=int(eos))
    with eng:
        out = list(eng.submit(prompts[0], max_new_tokens=6)
                   .result(timeout=120))
    # stops at (and includes) the EOS token
    assert [int(t) for t in out] == ref[:3]


def test_continuous_batching_joins_mid_flight(lm, prompts):
    """Iteration-level scheduling: short requests submitted AFTER a
    long one complete while the long stream is still decoding (they
    join freed slots at step boundaries instead of waiting for the
    batch to drain)."""
    eng = GenerationEngine(lm, slots=2)
    with eng:
        long_s = eng.submit(prompts[0], max_new_tokens=24)
        shorts = [eng.submit(p, max_new_tokens=2) for p in prompts[1:5]]
        for s in shorts:
            s.result(timeout=120)
        # 4 shorts need ~2 steps each; the long needs 23 decode steps —
        # it cannot have finished when the last short's future resolved
        assert not long_s.future.done()
        out = long_s.result(timeout=120)
    assert len(out) == 24
    # and the shorts got the same tokens as their reference decodes
    refs = [reference_decode(lm, p, 2) for p in prompts[1:5]]
    assert [list(int(t) for t in s.result()) for s in shorts] == refs


def test_cancel_mid_generation_frees_slot(lm, prompts):
    """A mid-generation cancel fails ONLY its own stream with
    GenerationCancelled and frees the KV slot for queued work."""
    eng = GenerationEngine(lm, slots=2)
    with eng:
        victim = eng.submit(prompts[0], max_new_tokens=24)
        other = eng.submit(prompts[1], max_new_tokens=6)
        it = iter(victim)
        got = [next(it), next(it)]          # let it produce a couple
        victim.cancel()
        with pytest.raises(GenerationCancelled):
            victim.result(timeout=120)
        assert len(got) == 2
        # the other stream is unaffected ...
        assert (list(int(t) for t in other.result(timeout=120))
                == reference_decode(lm, prompts[1], 6))
        # ... and the freed slot serves new work
        late = eng.submit(prompts[2], max_new_tokens=4)
        assert (list(int(t) for t in late.result(timeout=120))
                == reference_decode(lm, prompts[2], 4))
    snap = eng.stats()
    # a client cancel is NOT a dispatch error (its own counter)
    assert snap["cancelled"] == 1
    assert snap["errors"] == 0


def test_cancel_while_queued_never_prefills(lm, prompts):
    eng = GenerationEngine(lm, slots=2)
    # not started: everything stays queued
    s = eng.submit(prompts[0], max_new_tokens=4)
    s.cancel()
    assert s.future.cancelled()
    assert list(s) == []  # iterator terminates immediately
    eng.stop()


def test_queued_deadline_expires_before_prefill(lm, prompts):
    """PR 8 semantics carried over: a prompt still queued past its
    deadline fails with DeadlineExceeded AT a step boundary — while
    every slot is still busy (the decode loop reaps expiry every
    iteration; it does not wait for a slot to free) — and never burns
    a prefill."""
    eng = GenerationEngine(lm, slots=2)
    with eng:
        # occupy both slots with long generations
        longs = [eng.submit(p, max_new_tokens=20) for p in prompts[:2]]
        doomed = eng.submit(prompts[2], max_new_tokens=4,
                            deadline_ms=0.001)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        # the expiry fired while the long generations were in flight,
        # not when a slot freed
        assert not all(s.future.done() for s in longs)
        for s in longs:
            s.result(timeout=120)
    assert eng.stats()["expired"] == 1


def test_admission_reject_and_stop_before_start(lm, prompts):
    """The bounded queue + reject policy apply per REQUEST, and a
    stop() before start() fails queued streams with SheddedError."""
    eng = GenerationEngine(lm, slots=2, max_queue_requests=2,
                           admission="reject", max_new_tokens=4)
    s1 = eng.submit(prompts[0])
    s2 = eng.submit(prompts[1])
    with pytest.raises(OverloadError):
        eng.submit(prompts[2])
    assert eng.stats()["rejected"] == 1
    eng.stop()
    for s in (s1, s2):
        with pytest.raises(SheddedError):
            s.result(timeout=10)
    with pytest.raises(RuntimeError):  # single-use, like ServingEngine
        eng.start()


def test_submit_validation(lm):
    eng = GenerationEngine(lm, slots=2)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(np.ones((SEQ,), np.int32), max_new_tokens=4)
    # an explicit 0 must hit the guard, not silently fall back to the
    # config default
    with pytest.raises(ValueError, match=">= 1"):
        eng.submit(np.ones((4,), np.int32), max_new_tokens=0)
    eng.stop()


def test_lstm_lm_engine_matches_reference():
    """The RNN-cell workload end to end: state-carry decode through the
    engine equals the replicated reference."""
    from flexflow_tpu.models import build_lstm_lm
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=5)
    model = build_lstm_lm(cfg, vocab_size=VOCAB, embed_dim=24,
                          hidden_dim=24, num_layers=1, seq_len=SEQ)[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    model.init_layers(seed=5)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, VOCAB, 4).astype(np.int32)
               for _ in range(3)]
    with GenerationEngine(model, slots=2, max_new_tokens=5) as eng:
        outs = [list(int(t) for t in eng.submit(p).result(timeout=120))
                for p in prompts]
    assert outs == [reference_decode(model, p, 5) for p in prompts]


def test_decoder_rejects_unsupported_graphs():
    from flexflow_tpu.models import build_transformer
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32")
    clf = build_transformer(cfg, num_layers=1, d_model=32, num_heads=2,
                            d_ff=64, seq_len=16, vocab_size=VOCAB)[0]
    clf.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    with pytest.raises(ValueError, match="classifier|per-token"):
        GraphDecoder(clf, 2, 16)
    with pytest.raises(ValueError, match="slots"):
        GraphDecoder(clf, 1, 16)


# ---------------------------------------------------------------------
# the serving contract (ISSUE 29): a layer's serving form lives in the
# layer — nothing under flexflow_tpu/ knows the ops defined here
# ---------------------------------------------------------------------
class PrefixMean(Op):
    """``y[t] = mean(x[0..t])`` — a layer kind the package has never
    heard of, with a fixed per-slot state (the running sum; the count is
    the position).  Forward adds position by position in a scan, as the
    token step does, so both make the same sums."""

    op_type = OpType.ELEMENT_UNARY

    def __init__(self, name, x):
        super().__init__(name, [x])
        self._add_output(x.shape, x.dtype)

    @staticmethod
    def _sums(x):
        n, _, d = x.shape

        def add(total, x_t):
            total = total + x_t
            return total, total

        _, sums = jax.lax.scan(add, jnp.zeros((n, d), jnp.float32),
                               jnp.transpose(x, (1, 0, 2)))
        return jnp.transpose(sums, (1, 0, 2))

    def forward(self, params, inputs, ctx):
        x = inputs[0]
        count = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32)
        return [self._sums(x) / count[None, :, None]]

    def serve_state(self, slots, num_pages, page_size, mesh_sizes):
        return {"kind": "state",
                "shapes": {"sum": (slots, self.inputs[0].shape[-1])},
                "entries": {"sum": (None, None)}, "dtype": "f32"}

    def serve_check(self, max_seq):
        pass

    def serve_step(self, params, inputs, state, where, ctx):
        x = inputs[0]
        if where.kind == "chunk":       # a whole prompt of one slot
            sums = self._sums(x)
            last = jax.lax.dynamic_index_in_dim(
                sums, where.length - 1, axis=1, keepdims=False)
            count = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32)
            return ([sums / count[None, :, None]],
                    {"sum": jax.lax.dynamic_update_slice(
                        state["sum"], last, (where.slot, 0))})
        total = state["sum"] + x[:, 0]
        count = (where.pos + 1).astype(jnp.float32)
        return [(total / count[:, None])[:, None]], {"sum": total}


def test_engine_serves_a_layer_kind_defined_outside_the_package():
    """What adding a layer kind costs: the op's own three members.  An
    op defined HERE, keeping a fixed per-slot array, sits in a small LM
    graph and GenerationEngine serves it token for token equal to the
    full forward — whole-prompt prefill (its state cannot page), its
    bytes in the plan the static gates charge — and no file under
    flexflow_tpu/ names it."""
    import pathlib
    from flexflow_tpu.analysis.kv_memory import kv_page_plan
    d = 24
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=9)
    model = ff.FFModel(cfg)
    tokens = model.create_tensor((cfg.batch_size, SEQ), dtype="int32",
                                 name="tokens")
    t = model.embedding(tokens, VOCAB, d, aggr="none", name="tok_embedding")
    t = model._register(PrefixMean("prefix_mean", t)).outputs[0]
    t = model.dense(t, d, activation="relu", name="mix")
    model.softmax(model.dense(t, VOCAB, name="vocab_projection"))
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    model.init_layers(seed=9)
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, VOCAB, int(k)).astype(np.int32)
               for k in (3, 7, 4, 5)]
    with GenerationEngine(model, slots=2, max_new_tokens=6) as eng:
        dec = eng._decoder
        assert not dec.supports_chunking and not dec.pageable
        assert not eng.prefix_cache_enabled and eng.prefill_chunk == 0
        assert dec.layout == {"prefix_mean": {
            "kind": "state", "shapes": {"sum": (2, d)},
            "entries": {"sum": (None, None)}, "dtype": "f32"}}
        outs = [list(int(t) for t in eng.submit(p).result(timeout=120))
                for p in prompts]
        assert eng.kv_cache_bytes == 2 * d * 4
    assert outs == [reference_decode(model, p, 6) for p in prompts]
    plan = kv_page_plan(model.layers, {"n": 1}, 2, SEQ, kv_dtype_bytes=4)
    assert plan["state_bytes"] == 2 * d * 4 and plan["pool_bytes"] == 0
    pkg = pathlib.Path(ff.__file__).parent
    named = [str(f) for f in pkg.rglob("*.py")
             if re.search("prefix_?mean", f.read_text(), re.I)]
    assert named == []


_KV = {"kind": "kv", "dtype": "compute"}
_STATE = {"kind": "state", "dtype": "f32"}


def _kv(pages, c):
    shape, entries = (pages, 16, 32), (None, None, c)
    return dict(_KV, shapes={"k": shape, "v": shape},
                entries={"k": entries, "v": entries})


def _hc(hidden, n, c):
    shape, entries = (4, hidden), (n, c)
    return dict(_STATE, shapes={"h": shape, "c": shape},
                entries={"h": entries, "c": entries})


@pytest.mark.parametrize("mesh", [None, {"n": 2, "c": 2}],
                         ids=["no-mesh", "n2xc2"])
@pytest.mark.parametrize("which", ["transformer_lm", "lstm_lm"])
def test_kv_cache_layout_is_what_it_was_before_the_ops_declared_it(
        which, mesh):
    """The decision moved into the ops (ISSUE 29); its answer did not.
    The layout of each LM written out as kv_memory.py used to build it:
    4 slots x 32 positions in pages of 16, and 5 pages where the pool
    is given."""
    from flexflow_tpu.analysis.kv_memory import kv_cache_layout
    from flexflow_tpu.models import build_lstm_lm, build_transformer_lm
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32")
    c = "c" if mesh else None
    if which == "transformer_lm":
        model = build_transformer_lm(cfg, num_layers=2, d_model=32,
                                     num_heads=2, d_ff=64, seq_len=SEQ,
                                     vocab_size=VOCAB)[0]
        want = lambda pages: {"attention_0": _kv(pages, c),  # noqa: E731
                              "attention_1": _kv(pages, c)}
    else:
        model = build_lstm_lm(cfg, vocab_size=VOCAB, embed_dim=24,
                              hidden_dim=24, num_layers=2, seq_len=SEQ)[0]
        n = "n" if mesh else None
        want = lambda pages: {"lm_lstm_0": _hc(24, n, c),    # noqa: E731
                              "lm_lstm_1": _hc(24, n, c)}
    assert kv_cache_layout(model.layers, mesh, 4, SEQ) == want(8)
    assert kv_cache_layout(model.layers, mesh, 4, SEQ, page_size=16,
                           num_pages=5) == want(5)


def test_decoder_refuses_an_op_that_cannot_step_by_its_name():
    """Neither position-wise nor with a step of its own: refused by
    GraphDecoder at construction, the op's name in the message — never
    served through a forward that mixes positions."""
    class Shift(Op):
        op_type = OpType.RESHAPE

        def __init__(self, name, x):
            super().__init__(name, [x])
            self._add_output(x.shape, x.dtype)

        def forward(self, params, inputs, ctx):
            return [jnp.roll(inputs[0], 1, axis=1)]

    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32")
    model = ff.FFModel(cfg)
    tokens = model.create_tensor((4, SEQ), dtype="int32", name="tokens")
    t = model.embedding(tokens, VOCAB, 16, aggr="none")
    t = model._register(Shift("shift_by_one", t)).outputs[0]
    model.softmax(model.dense(t, VOCAB))
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    with pytest.raises(ValueError, match=r"shift_by_one \(reshape\) has no "
                                         r"single-position decode path"):
        GraphDecoder(model, 2, SEQ)


# ---------------------------------------------------------------------
# strategy-sharded serving (the acceptance's {n>1} half)
# ---------------------------------------------------------------------
def _write_tp_strategy(path):
    from flexflow_tpu.config import DeviceType, ParallelConfig
    from flexflow_tpu.strategy.proto import save_strategy_file
    strategies = {}
    for name in ["attention_0", "attention_1", "ffn_up_0", "ffn_up_1",
                 "ffn_down_0", "ffn_down_1", "tok_embedding"]:
        strategies[name] = ParallelConfig(
            device_type=DeviceType.DEVICE, dims=(2, 1, 2),
            device_ids=tuple(range(4)))
    save_strategy_file(str(path), strategies)
    return strategies


def test_sharded_engine_matches_replicated_reference(tmp_path, lm,
                                                     prompts):
    """Acceptance pin, sharded half: ``from_strategy`` on a searched-
    style TP strategy ({n:2, c:2} — heads over 'c', slots over 'n')
    produces outputs identical to the replicated predict-style decode.
    The KV cache shards with the mesh: per-device bytes halve twice."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    pb = tmp_path / "gen_tp.pb"
    _write_tp_strategy(pb)
    m2 = _build_lm()  # same seed -> same init values as `lm`
    # fresh (compiled) model: from_strategy re-places the live params
    eng = GenerationEngine.from_strategy(m2, str(pb), slots=4,
                                         max_new_tokens=6)
    assert m2.mesh.axis_size("c") == 2 and m2.mesh.axis_size("n") == 2
    with eng:
        outs = [list(int(t) for t in
                     eng.submit(p, max_new_tokens=6).result(timeout=180))
                for p in prompts[:4]]
        k_pool = eng._caches["attention_0"]["k"]
        # the folded dim shards over c: 2 heads x 16 -> ONE whole head
        # (16 contiguous lanes) a shard; pages and rows stay whole
        assert k_pool.shape == (eng.num_pages, eng.page_size, 32)
        assert {sh.data.shape for sh in k_pool.addressable_shards} \
            == {(eng.num_pages, eng.page_size, 16)}
    refs = [reference_decode(lm, p, 6) for p in prompts[:4]]
    assert outs == refs
    # sharded pool accounting: heads over c (x2); the page dim is
    # REPLICATED over n (pages are interchangeable across slots — a
    # slot-sharded pool could not share a prefix page fleet-wide), so
    # the paged pool halves once, not twice like the old dense cache
    from flexflow_tpu.analysis import kv_cache_bytes
    rep = kv_cache_bytes(m2.layers, {"n": 1}, 4, SEQ, kv_dtype_bytes=4)
    shd = kv_cache_bytes(m2.layers, dict(m2.mesh.sizes), 4, SEQ,
                         kv_dtype_bytes=4)
    assert shd == rep / 2
    assert eng.kv_cache_bytes == shd


def test_from_strategy_on_fresh_model(tmp_path, lm, prompts):
    """The primary documented flow: hand ``from_strategy`` an
    UNCOMPILED model — it compiles against the strategy (ffcheck
    verified), infers the strategy's mesh, and inits sharded."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from flexflow_tpu.models import build_transformer_lm
    pb = tmp_path / "gen_tp.pb"
    _write_tp_strategy(pb)
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=0)
    fresh = build_transformer_lm(cfg, num_layers=2, d_model=32,
                                 num_heads=2, d_ff=64, seq_len=SEQ,
                                 vocab_size=VOCAB)[0]
    assert not fresh._compiled
    eng = GenerationEngine.from_strategy(fresh, str(pb), slots=4,
                                         max_new_tokens=4)
    assert fresh._compiled and fresh.mesh.axis_size("c") == 2
    with eng:
        out = list(int(t) for t in
                   eng.submit(prompts[0], max_new_tokens=4)
                   .result(timeout=180))
    assert out == reference_decode(lm, prompts[0], 4)


# ---------------------------------------------------------------------
# KV-cache memory accounting: runtime == analysis (the ONE scalar)
# ---------------------------------------------------------------------
def test_kv_cache_bytes_matches_real_allocation(lm):
    from flexflow_tpu.analysis import kv_cache_bytes
    dec = GraphDecoder.for_model(lm, 2, SEQ)
    caches = dec.init_cache()
    real = sum(int(leaf.nbytes) for sub in caches.values()
               for leaf in sub.values())
    predicted = kv_cache_bytes(lm.layers, {"n": 1}, 2, SEQ,
                               kv_dtype_bytes=4)  # f32 compute
    assert real == predicted


def test_kv_bytes_flip_ff108_and_ff121(lm):
    """The FF108 HBM gate and FF121 timeline see the engine's KV
    scalar: a budget that fits the model alone overflows once the
    generation deployment's cache is charged."""
    import dataclasses

    from flexflow_tpu.analysis import kv_cache_bytes, verify
    from flexflow_tpu.config import ParallelConfig
    from flexflow_tpu.search.cost_model import spec_for_device

    strategies = {lm.layers[2].name: ParallelConfig.data_parallel(1, 3)}
    base = verify(lm.layers, strategies, mesh_shape={"n": 1},
                  num_devices=1, parameters=lm.parameters,
                  spec=spec_for_device(), check_resharding=False)
    base_codes = {d.code for d in base.errors + base.warnings}
    # a budget just above the model's own peak
    peak_fit = dataclasses.replace(
        spec_for_device(), hbm_capacity=2e9)
    kv = kv_cache_bytes(lm.layers, {"n": 1}, 4096, SEQ,
                        kv_dtype_bytes=4)
    rep = verify(lm.layers, strategies, mesh_shape={"n": 1},
                 num_devices=1, parameters=lm.parameters,
                 spec=peak_fit, check_resharding=False,
                 extra_state_bytes=50 * kv)
    codes = {d.code for d in rep.errors + rep.warnings}
    assert "FF108" in codes and "FF121" in codes
    assert "FF108" not in base_codes
    kv_diag = next(d for d in rep.errors if d.code == "FF108")
    assert "KV cache" in kv_diag.message


def test_explain_reports_kv_section(lm):
    from flexflow_tpu.analysis import explain_report
    from flexflow_tpu.config import ParallelConfig
    strategies = {lm.layers[2].name: ParallelConfig.data_parallel(1, 3)}
    plain = explain_report("lm", lm.layers, strategies,
                           mesh_shape={"n": 1})
    rep = explain_report("lm", lm.layers, strategies,
                         mesh_shape={"n": 1}, dtype_bytes=4,
                         serve_slots=8, serve_seq=SEQ)
    assert "kv_cache" in rep and rep["kv_cache"]["slots"] == 8
    kv = rep["kv_cache"]["bytes_per_device"]
    assert kv > 0
    assert (rep["memory_timeline"]["state_bytes"]
            == pytest.approx(plain["memory_timeline"]["state_bytes"]
                             + kv))


# ---------------------------------------------------------------------
# paged KV cache, shared-prefix reuse & chunked prefill (ISSUE 15)
# ---------------------------------------------------------------------
def test_page_pool_refcounts_and_high_water():
    from flexflow_tpu.serving.generation.pages import KVPagePool
    pool = KVPagePool(4, page_size=16)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.pages_in_use == 2
    assert pool.high_water == 2 and pool.no_page == 4
    pool.ref(a)
    assert not pool.release(a)      # still referenced
    assert pool.release(a)          # back to the free list
    assert pool.pages_in_use == 1
    c, d, e = pool.alloc(), pool.alloc(), pool.alloc()
    assert pool.alloc() is None     # exhausted, never blocks
    assert pool.high_water == 4
    assert {c, d, e} | {b} == {0, 1, 2, 3}


def test_prefix_trie_lookup_insert_evict():
    from flexflow_tpu.serving.generation.pages import (KVPagePool,
                                                       PrefixCache)
    pool = KVPagePool(8, page_size=4)
    trie = PrefixCache(pool)
    toks = np.arange(100, 112, dtype=np.int32)  # 3 full pages of 4
    # only pages strictly covering [0, len-1) are shareable: a 12-token
    # prompt caches pages 0..1 (page 2 holds position 11 — recomputed)
    assert trie._pages_of(toks, 4) == [(100, 101, 102, 103),
                                       (104, 105, 106, 107)]
    p0, p1 = pool.alloc(), pool.alloc()
    assert trie.insert(toks, [p0, p1]) == 2
    assert pool.refcount(p0) == 2   # slot ref + trie ref
    # a prompt extending the prefix hits both pages (one ref each)
    ext = np.concatenate([toks, np.array([7, 8], np.int32)])
    hits = trie.lookup(ext)
    assert hits == [p0, p1] and pool.refcount(p0) == 3
    # divergence INSIDE page 1 stops the walk after page 0 — sharing is
    # all-or-nothing per page, so no copy-on-write case can arise
    div = toks.copy()
    div[5] = 99
    assert trie.lookup(div) == [p0]
    # drop every non-trie ref: p0 holds alloc + ext-lookup + div-lookup,
    # p1 holds alloc + ext-lookup
    for pg in (p0, p0, p0, p1, p1):
        pool.release(pg)
    assert pool.refcount(p0) == 1 and pool.refcount(p1) == 1
    # LRU eviction frees unreferenced LEAF pages only, oldest first:
    # p1 (leaf) goes before p0 (interior, then leaf)
    assert trie.evict_one() and pool.refcount(p1) == 0
    assert trie.evict_one() and pool.refcount(p0) == 0
    assert not trie.evict_one() and len(trie) == 0
    assert trie.evictions == 2


def _walk_cache(pool):
    """The oracle of the leaf order: a ``PrefixCache`` whose ``evict``
    is the one PR 40 deleted from the package, a depth-first walk of
    the WHOLE trie for the leaves only the trie references, sorted by
    ``last_used``, walked again when the list runs dry."""
    from flexflow_tpu.serving.generation.pages import PrefixCache

    class WalkCache(PrefixCache):
        def _evictable(self):
            out = []
            stack = list(self._root.values())
            while stack:
                node = stack.pop()
                if node.children:
                    stack.extend(node.children.values())
                elif self.pool.refcount(node.page) == 1:
                    out.append(node)
            return out

        def evict(self, count):
            freed = 0
            while freed < count:
                victims = sorted(self._evictable(),
                                 key=lambda n: n.last_used)
                if not victims:
                    break
                for node in victims:
                    if freed >= count:
                        break
                    self._evict_node(node)
                    freed += 1
            return freed

    return WalkCache(pool)


def _logged(trie):
    """``trie`` with its victims written down, in order: the page and
    the token chain of every node ``_evict_node`` removes."""
    log = []
    evict_node = trie._evict_node

    def spy(node):
        chain, n = [], node
        while n is not None:
            chain.append(n.key)
            n = n.parent
        log.append((node.page, tuple(reversed(chain))))
        evict_node(node)

    trie._evict_node = spy
    return log


@pytest.mark.parametrize("seed", range(12))
def test_leaf_order_evicts_what_the_walk_evicted(seed):
    """ISSUE 40's property: on branching tries (shared prefixes, leaves
    live slots hold, parents a batch exposes, ``evict`` past what is
    evictable, ``clear``) the heap of leaves gives the victims of the
    whole-trie walk, in its order, and leaves the same trie and the
    same pool behind."""
    from flexflow_tpu.serving.generation.pages import (KVPagePool,
                                                       PrefixCache)
    rng = np.random.default_rng(4000 + seed)
    # every third seed hits much on a pool that never fills and evicts
    # once in 250 steps, so that the order outgrows the trie and is
    # rebuilt from its live entries; the others run a small pool full
    roomy = seed % 3 == 0
    page, pages = 2, 400 if roomy else 48
    sides = []
    for make in (PrefixCache, _walk_cache):
        pool = KVPagePool(pages, page_size=page)
        trie = make(pool)
        sides.append((pool, trie, _logged(trie)))
    slots = []      # what live requests hold: one page list a side
    prompts = []    # every prompt seen: later ones branch off them

    def both(fn):
        got = [fn(pool, trie) for pool, trie, _ in sides]
        assert got[0] == got[1]
        return got

    def admit(pool, trie):
        """A join as the engine makes it: borrow the cached prefix,
        evict the deficit in one batch, allocate, promote."""
        held = trie.lookup(prompt)
        need = (len(prompt) - 1) // page + 1
        deficit = need - len(held) - pool.pages_free
        if deficit > 0:
            trie.evict(deficit)
        while len(held) < need:
            pg = pool.alloc()
            if pg is None:      # every page backs a live request
                for p in held:
                    pool.release(p)
                return None
            held.append(pg)
        trie.insert(prompt, held[:(len(prompt) - 1) // page])
        return held

    share = ({"admit": .3, "finish": .2, "touch": .5} if roomy else
             {"admit": .55, "finish": .25, "evict": .12, "touch": .07,
              "clear": .01})
    rebuilt = 0
    for step in range(1500 if roomy else 400):
        op = rng.choice(list(share), p=list(share.values()))
        if roomy and step % 250 == 249:
            op = "evict"
        before = len(sides[0][1]._leaves)
        if op == "admit":
            prompt = list(rng.integers(0, 3, int(rng.integers(2, 13))))
            if prompts and rng.random() < 0.6:
                base = prompts[int(rng.integers(len(prompts)))]
                prompt = base[:int(rng.integers(1, len(base) + 1))] \
                    + prompt[:int(rng.integers(1, 7))]
            prompts.append(prompt)
            got = both(admit)
            if got[0] is not None:
                slots.append(got)
        elif op == "finish" and slots:
            for (pool, _, _), held in zip(
                    sides, slots.pop(int(rng.integers(len(slots))))):
                for p in held:
                    pool.release(p)
        elif op == "evict":
            k = int(rng.choice([1, 1, 2, 3, 5, 8, 10 * pages],
                               p=[.2, .2, .15, .15, .15, .1, .05]))
            both(lambda pool, trie: trie.evict(k))
        elif op == "touch" and prompts:
            prompt = prompts[int(rng.integers(len(prompts)))]

            def touch(pool, trie):
                hit = trie.lookup(prompt)
                for p in hit:
                    pool.release(p)
                return hit
            both(touch)
        elif op == "clear":
            both(lambda pool, trie: trie.clear())
        (pool, trie, log), (wpool, walk, wlog) = sides
        assert log == wlog
        assert (trie.evictions, len(trie)) == (walk.evictions, len(walk))
        assert pool._free == wpool._free and pool._refs == wpool._refs
        # the order holds no more than the trie's size twice over
        assert len(trie._leaves) <= 2 * len(trie) + 64
        rebuilt += op != "evict" and len(trie._leaves) < before
    # the run evicted enough to tell, and a roomy one rebuilt the order
    assert len(sides[0][2]) > (5 if roomy else 20)
    assert rebuilt or not roomy


def _full_pool(pool, trie, chains, nodes, held):
    """A pool run full the way ``gpt1.serve.closed-128`` runs it:
    ``nodes`` cached prompt pages in ``chains`` distinct prompts, no
    shared prefix, the YOUNGEST ``held`` prompts still live (their
    slots hold every page, so their leaves are held leaves).  Returns
    the page lists of the live prompts."""
    size = pool.page_size
    lengths = [nodes // chains + (i < nodes % chains)
               for i in range(chains)]
    live = []
    for i, n in enumerate(lengths):
        prompt = [i] + [7] * (n * size)      # n full pages, and one on
        pages = [pool.alloc() for _ in range(n)]
        assert trie.insert(prompt, pages) == n
        if i < chains - held:
            for p in pages:
                pool.release(p)
        else:
            live.append(pages)
    assert len(trie) == nodes
    return live


# what one eviction may pop off the leaf order, stale and held entries
# included, where the walk visited every node of the trie (4 000 here)
SCANNED_A_VICTIM = 4


def test_an_eviction_costs_pops_not_a_walk():
    """The cost as a COUNT: 4 000 cached pages in 650 prompts, 96 of
    them live; 200 single evictions scan a small constant each."""
    from flexflow_tpu.serving.generation.pages import (KVPagePool,
                                                       PrefixCache)
    pool = KVPagePool(4000, page_size=4)
    trie = PrefixCache(pool)
    _full_pool(pool, trie, chains=650, nodes=4000, held=96)
    assert pool.pages_free == 0
    for call in range(1, 201):
        assert trie.evict(1) == 1
        assert trie.evict_scanned <= SCANNED_A_VICTIM * call
    assert trie.evictions == 200 and len(trie) == 3800
    # the leaves live requests hold are young: none was asked about
    assert trie.evict_scanned == 200
    # a cache that is hit often and evicts seldom pushes an entry a hit
    # and pops none: the order stays within twice the trie all the same
    hot = [649] + [7] * 28
    for _ in range(10000):
        for p in trie.lookup(hot):
            pool.release(p)
    assert len(trie._leaves) <= 2 * len(trie) + 64
    assert trie.evict(1) == 1 and trie.evict_scanned <= 201 + 96


def test_grow_active_pages_scans_a_few_nodes(lm):
    """A boundary of the host-bound cell: 128 decoding slots on a pool
    full of cached prompts, 8 of them at a page edge.  Each brings its
    own deficit of one; together they scan under 40 nodes."""
    from flexflow_tpu.serving.generation.engine import _Slot
    eng = GenerationEngine(lm, slots=128, page_size=4, num_pages=4000,
                           prefix_cache="on")
    pool, trie = eng._pool, eng._prefix
    live = _full_pool(pool, trie, chains=650, nodes=3904, held=96)
    # 96 slots decode on their cached prompt's pages, 32 on pages of
    # their own (prompts under a page: nothing of theirs is cached)
    live += [[pool.alloc() for _ in range(3)] for _ in range(32)]
    assert pool.pages_free == 0 and len(live) == 128
    edge = set(range(0, 128, 16))
    for i, pages in enumerate(live):
        s = _Slot(None, np.zeros(1, np.int32), pages, 4, 0.0)
        s.prefilling = False
        # the next position opens a new page at an edge, else not
        s.length = len(pages) * 4 - (0 if i in edge else 2)
        eng._slots_state[i] = s
        eng._table[i, :len(pages)] = pages
    before = [len(p) for p in live]
    eng._grow_active_pages()
    grown = [len(s.pages) - n
             for s, n in zip(eng._slots_state, before)]
    assert grown == [int(i in edge) for i in range(128)]
    assert trie.evictions == 8 and pool.pages_free == 0
    assert trie.evict_scanned < 40
    snap = eng._pool_stats()
    assert (snap["evictions"], snap["evict_scanned"]) \
        == (8, trie.evict_scanned)


def test_prefix_cache_on_off_bit_identical(lm):
    """THE ISSUE 15 correctness anchor: the same shared-prefix trace
    decodes to bit-identical tokens with the prefix cache on and off,
    and both match the dense predict-style reference."""
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, VOCAB, 20).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, VOCAB, 3).astype(np.int32)])
               for _ in range(4)]

    def run(cache):
        eng = GenerationEngine(lm, slots=2, max_new_tokens=5,
                               prefix_cache=cache)
        with eng:
            streams = [eng.submit(p) for p in prompts]
            outs = [list(int(t) for t in s.result(timeout=120))
                    for s in streams]
            snap = eng.stats()
        return outs, snap

    outs_on, snap_on = run("on")
    outs_off, snap_off = run("off")
    assert outs_on == outs_off
    assert outs_on == [reference_decode(lm, p, 5) for p in prompts]
    # the cache actually engaged: 20-token prefix = one full 16-page
    # shared by the later streams; off-arm saw zero hits
    assert snap_on["prefix_hit_tokens"] > 0
    assert snap_off["prefix_hit_tokens"] == 0
    assert snap_on["prefix_hit_rate"] > 0
    # and fewer pages were ever live with sharing on
    assert (snap_on["kv_pages_high_water"]
            <= snap_off["kv_pages_high_water"])
    # every submitted request accounted for exactly once, in both arms
    for snap in (snap_on, snap_off):
        assert snap["submitted"] == snap["requests"] == len(prompts)


def test_chunked_prefill_bit_identical(lm, prompts):
    """Chunked prefill (including a chunk size that does NOT divide
    the prompt or the page size) decodes bit-identically to the
    monolithic engine and the reference."""
    refs = [reference_decode(lm, p, 5) for p in prompts[:4]]
    for chunk in (0, 3, 4):
        eng = GenerationEngine(lm, slots=2, max_new_tokens=5,
                               prefill_chunk=chunk)
        with eng:
            outs = [list(int(t) for t in
                         eng.submit(p).result(timeout=120))
                    for p in prompts[:4]]
        assert outs == refs, f"chunk={chunk}"
    # chunked long prompt: more than one chunk actually ran
    eng = GenerationEngine(lm, slots=2, max_new_tokens=3,
                           prefill_chunk=4, prefix_cache="off")
    long_p = np.asarray(
        np.random.default_rng(8).integers(1, VOCAB, 14), np.int32)
    with eng:
        out = list(int(t) for t in
                   eng.submit(long_p).result(timeout=120))
        snap = eng.stats()
    assert out == reference_decode(lm, long_p, 3)
    assert snap["prefill_chunks"] >= 4  # 14 tokens / chunks of 4


def test_prefix_cache_with_chunked_prefill(lm):
    """Prefix hits + chunked suffix prefill compose: the suffix beyond
    the shared page prefills in chunks, tokens stay reference-exact."""
    rng = np.random.default_rng(9)
    prefix = rng.integers(1, VOCAB, 16).astype(np.int32)  # one page
    p1 = np.concatenate([prefix, rng.integers(1, VOCAB, 9)
                         .astype(np.int32)])
    p2 = np.concatenate([prefix, rng.integers(1, VOCAB, 7)
                         .astype(np.int32)])
    eng = GenerationEngine(lm, slots=2, max_new_tokens=4,
                           prefill_chunk=3, prefix_cache="on")
    with eng:
        o1 = list(int(t) for t in eng.submit(p1).result(timeout=120))
        o2 = list(int(t) for t in eng.submit(p2).result(timeout=120))
        snap = eng.stats()
    assert o1 == reference_decode(lm, p1, 4)
    assert o2 == reference_decode(lm, p2, 4)
    assert snap["prefix_hit_tokens"] == 16  # p2 reused p1's page


def test_cancel_between_prefill_pack_and_scatter(lm, prompts,
                                                 monkeypatch):
    """ISSUE 15 satellite: a cancel() landing DURING the prefill
    dispatch — after the engine claimed the future and packed the
    chunk, before its token scatter — must reclaim the slot AND its
    pages, fail only that stream, and leave concurrent streams
    reference-exact.  (monkeypatch on the engine's decoder instance
    keeps the shared compiled programs intact for other tests.)"""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=6,
                           prefix_cache="off")
    state = {}
    orig = eng._decoder.prefill_fn

    def hooked(bucket):
        fn = orig(bucket)

        def wrapper(*a, **kw):
            v = state.get("stream")
            if v is not None and not state.get("fired"):
                state["fired"] = True
                v.cancel()  # between the pack and the scatter
            return fn(*a, **kw)

        return wrapper

    monkeypatch.setattr(eng._decoder, "prefill_fn", hooked)
    with eng:
        ok = eng.submit(prompts[0], max_new_tokens=6)
        list(ok)  # victim arms only after the first stream is through
        state["stream"] = victim = eng.submit(prompts[1],
                                              max_new_tokens=6)
        with pytest.raises(GenerationCancelled):
            victim.result(timeout=120)
        assert state["fired"]
        # pages reclaimed: a follow-up stream serves reference-exact
        late = eng.submit(prompts[2], max_new_tokens=6)
        assert (list(int(t) for t in late.result(timeout=120))
                == reference_decode(lm, prompts[2], 6))
        assert eng._pool.pages_in_use == 0  # everything reclaimed
    snap = eng.stats()
    assert snap["cancelled"] == 1 and snap["errors"] == 0
    assert (list(int(t) for t in ok.result())
            == reference_decode(lm, prompts[0], 6))


def test_prefix_eviction_under_pool_pressure(lm):
    """An undersized pool LRU-evicts unreferenced cached-prefix pages
    instead of failing streams; tokens stay reference-exact and the
    evictions counter records it."""
    rng = np.random.default_rng(11)
    # four DISTINCT one-page prefixes on a 4-page pool: by the fourth
    # stream the trie holds 3 cached prefix pages, a joining stream
    # needs 2 fresh pages, and only LRU eviction of the oldest cached
    # prefix can make room
    prefs = [rng.integers(1, VOCAB, 16).astype(np.int32)
             for _ in range(4)]
    ps = [np.concatenate(
        [pref, rng.integers(1, VOCAB, 3).astype(np.int32)])
        for pref in prefs]
    eng = GenerationEngine(lm, slots=2, max_new_tokens=4,
                           num_pages=4, prefix_cache="on")
    with eng:
        outs = [list(int(t) for t in
                     eng.submit(p).result(timeout=120)) for p in ps]
        snap = eng.stats()
    assert outs == [reference_decode(lm, p, 4) for p in ps]
    assert snap["evictions"] >= 1
    assert snap["errors"] == 0 and snap["shed"] == 0


def test_kv_pages_exhausted_sheds_only_one_stream(lm):
    """A pool that genuinely cannot serve every concurrent stream
    sheds with KVCacheExhausted — only the starved stream fails, the
    rest complete reference-exact."""
    from flexflow_tpu.serving.errors import KVCacheExhausted
    rng = np.random.default_rng(12)
    # 2 pages of 16 on 2 slots, streams needing 2 pages each (prompt 4
    # + 20 new tokens crosses position 16): concurrent streams cannot
    # both fit
    ps = [rng.integers(1, VOCAB, 4).astype(np.int32) for _ in range(2)]
    eng = GenerationEngine(lm, slots=2, max_new_tokens=20, num_pages=2,
                           prefix_cache="off")
    results = []
    with eng:
        streams = [eng.submit(p) for p in ps]
        for s in streams:
            try:
                results.append(list(int(t) for t in
                                    s.result(timeout=120)))
            except KVCacheExhausted:
                results.append("shed")
        snap = eng.stats()
    assert results.count("shed") == 1
    good = next(i for i, r in enumerate(results) if r != "shed")
    assert results[good] == reference_decode(lm, ps[good], 20)
    assert snap["shed"] == 1 and snap["errors"] == 0
    # pool exhaustion is a SheddedError subclass: counted as shed
    assert eng._pool.pages_in_use == 0


@pytest.mark.parametrize("which,folded,kv_bytes", [
    ("lm", 32, 4), ("lm_lane_dense", 128, 4)])
def test_kv_page_plan_matches_real_pool(request, which, folded, kv_bytes):
    """Byte-for-byte, per leaf: the kv_memory page plan == the pool
    arrays the decoder actually allocates (the FF108/FF121/FF130
    scalar is total_bytes of this same plan), and every K/V leaf is
    stored lane-dense — (pages, page, heads * head_dim), page-major."""
    from flexflow_tpu.analysis.kv_memory import (kv_cache_layout,
                                                 kv_page_plan)
    lm = request.getfixturevalue(which)
    eng = GenerationEngine(lm, slots=2)
    dec = eng._decoder
    caches = dec.init_cache()
    layout = kv_cache_layout(lm.layers, {"n": 1}, 2, SEQ,
                             page_size=dec.page_size,
                             num_pages=dec.num_pages)
    assert layout == dec.layout
    for name, ent in layout.items():
        for leaf, shape in ent["shapes"].items():
            assert shape == (dec.num_pages, dec.page_size, folded)
            assert caches[name][leaf].shape == shape
            assert len(ent["entries"][leaf]) == len(shape)
    real = sum(int(leaf.nbytes) for sub in caches.values()
               for leaf in sub.values())
    plan = kv_page_plan(lm.layers, {"n": 1}, 2, SEQ,
                        kv_dtype_bytes=kv_bytes,
                        page_size=dec.page_size,
                        num_pages=dec.num_pages)
    assert real == plan["total_bytes"] == eng.kv_cache_bytes
    assert plan["pool_bytes"] + plan["state_bytes"] \
        == plan["total_bytes"]
    assert plan["num_pages"] == dec.num_pages
    # and the engine's high-water accounting uses the same page_bytes
    assert plan["page_bytes"] * plan["num_pages"] == plan["pool_bytes"]
    eng.stop()


@pytest.mark.parametrize("heads,c,entry", [
    (2, 1, None), (2, 2, "c"), (12, 4, "c"), (3, 2, None), (12, 8, None)])
def test_kv_layout_shards_the_folded_dim_by_whole_heads(heads, c, entry):
    """The folded K/V dim carries the tensor-parallel entry exactly
    where c divides the HEADS (a shard then holds heads / c whole
    heads, contiguous) — never where it merely divides heads x
    head_dim — and the per-device bytes of the plan follow."""
    from flexflow_tpu.analysis.kv_memory import (kv_cache_layout,
                                                 kv_page_plan)
    x = Tensor((4, SEQ, heads * 16), "float32")
    op = MultiHeadAttention("attn", x, x, x, heads * 16, heads,
                            causal=True)
    mesh = {"n": 1, "c": c}
    ent = kv_cache_layout([op], mesh, 4, SEQ)["attn"]
    pages = 4 * (SEQ // 16)
    assert ent["shapes"] == {"k": (pages, 16, heads * 16),
                             "v": (pages, 16, heads * 16)}
    assert ent["entries"]["k"] == ent["entries"]["v"] \
        == (None, None, entry)
    whole = kv_page_plan([op], {"n": 1}, 4, SEQ)["total_bytes"]
    assert whole == 2 * pages * 16 * heads * 16 * 2
    assert kv_page_plan([op], mesh, 4, SEQ)["total_bytes"] \
        == whole / (c if entry else 1)


def test_gen_stats_carry_pool_fields(lm, prompts):
    """gen_stats/stats() gain the ISSUE 15 fields (kv_pages_in_use,
    prefix_hit_rate, evictions, prefill_chunks) from the ONE engine
    pool — and the accounting defaults equal the dense baseline."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=3)
    with eng:
        eng.submit(prompts[0]).result(timeout=120)
        snap = eng.stats()
    for key in ("kv_pages_in_use", "kv_pages_high_water",
                "kv_page_size", "kv_num_pages", "kv_high_water_bytes",
                "prefix_hit_rate", "prefix_hit_tokens", "evictions",
                "evict_scanned", "prefill_chunks",
                "prefix_pages_cached"):
        assert key in snap, key
    assert snap["kv_pages_high_water"] >= 1
    assert snap["kv_high_water_bytes"] <= eng.kv_cache_bytes
    assert snap["prefill_chunks"] >= 1


# ---------------------------------------------------------------------
# FF_FAULT generation kinds (scripts/fault_matrix.sh runs this class)
# ---------------------------------------------------------------------
class TestGenerationFaults:
    @pytest.fixture
    def arm(self, monkeypatch):
        def _arm(spec):
            monkeypatch.setenv("FF_FAULT", spec)
            faults.reset()
        yield _arm
        monkeypatch.delenv("FF_FAULT", raising=False)
        faults.reset()

    def test_parse_generation_kinds(self):
        specs = faults.parse_faults(
            "serve_cancel_at_token:3;serve_slow_decode:2,ms=15")
        assert [s.kind for s in specs] == ["serve_cancel_at_token",
                                          "serve_slow_decode"]
        assert specs[1].extras["ms"] == "15"
        with pytest.raises(ValueError, match="integer"):
            faults.parse_faults("serve_cancel_at_token:soon")

    def test_generation_faults_accessor(self, arm):
        arm("serve_cancel_at_token:2;serve_slow_dispatch:1")
        kinds = [s.kind for s in faults.generation_faults()]
        assert kinds == ["serve_cancel_at_token"]
        # the serving engine's accessor sees only ITS kinds
        assert [s.kind for s in faults.serve_faults()] == \
            ["serve_slow_dispatch"]

    def test_slow_decode_uses_injected_sleep(self, arm, lm, prompts):
        arm("serve_slow_decode:3,ms=7")
        slept = []
        eng = GenerationEngine(lm, slots=2, sleep=slept.append)
        with eng:
            out = eng.submit(prompts[0], max_new_tokens=6)\
                .result(timeout=120)
        assert len(out) == 6
        assert slept == [0.007] * 3

    def test_cancel_at_token_frees_slot_and_fails_only_its_stream(
            self, arm, lm, prompts):
        """The injected mid-generation cancel: the FIRST stream to
        reach N tokens dies with GenerationCancelled, its KV slot
        frees, every other stream is untouched."""
        arm("serve_cancel_at_token:3")
        eng = GenerationEngine(lm, slots=2)
        with eng:
            victim = eng.submit(prompts[0], max_new_tokens=24)
            with pytest.raises(GenerationCancelled):
                victim.result(timeout=120)
            assert len(victim.tokens_so_far()) >= 3
            # the slot freed: a full-length follow-up stream serves
            # fine and matches the reference (fault fires once)
            ok = eng.submit(prompts[1], max_new_tokens=6)
            assert (list(int(t) for t in ok.result(timeout=120))
                    == reference_decode(lm, prompts[1], 6))

    def test_cancel_at_token_drops_the_step_in_flight(self, arm, lm,
                                                      prompts):
        """One step ahead a cancel is found when the tokens land: the
        victim holds exactly its N tokens, the token the step in flight
        computed for it is dropped, never emitted, and the stream beside
        it is served to its reference."""
        arm("serve_cancel_at_token:3")
        eng = GenerationEngine(lm, slots=2)
        with eng:
            victim = eng.submit(prompts[0], max_new_tokens=24)
            other = eng.submit(prompts[1], max_new_tokens=6)
            with pytest.raises(GenerationCancelled, match="after 3 token"):
                victim.result(timeout=120)
            assert ([int(t) for t in other.result(timeout=120)]
                    == reference_decode(lm, prompts[1], 6))
            snap = eng.stats()
        assert victim.tokens_so_far() == \
            reference_decode(lm, prompts[0], 3)
        assert snap["decode_pipeline"]["dropped_tokens"] == 1
        assert snap["cancelled"] == 1 and snap["errors"] == 0
        assert eng._pool.pages_in_use == 0

    def test_spec_draft_fail_demotes_without_failing_streams(
            self, arm, lm, draft_lm, prompts):
        """``FF_FAULT=spec_draft_fail:N``: the Nth draft dispatch
        raises — the engine demotes to plain decode (ONE serve_health
        fallback event, reason draft_error), NO stream fails, and
        every token still equals the non-speculative reference."""
        arm("spec_draft_fail:2")
        refs = [reference_decode(lm, p, 6) for p in prompts[:3]]
        eng = GenerationEngine(lm, slots=2, draft_model=draft_lm,
                               spec_gamma=2)
        with capture_events("serve") as events, eng:
            streams = [eng.submit(p, max_new_tokens=6)
                       for p in prompts[:3]]
            outs = [list(int(t) for t in s.result(timeout=120))
                    for s in streams]
            snap = eng.stats()
        assert outs == refs
        assert snap["spec"] == "fallback"
        assert snap["spec_fallbacks"] == 1
        assert snap["errors"] == 0 and snap["cancelled"] == 0
        ev = [e for e in events if e["event"] == "serve_health"
              and e.get("component") == "speculation"]
        assert len(ev) == 1
        assert ev[0]["status"] == "fallback"
        assert ev[0]["reason"] == "draft_error"
        assert "spec_draft_fail" in ev[0]["error"]


# ---------------------------------------------------------------------
# speculative decoding + real sampling (ISSUE 16)
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def draft_lm(lm):
    # same seed as `lm` -> identical weights: the draft's greedy
    # proposals all verify, so every window accepts (gamma-at-a-time)
    return _build_lm()


@pytest.fixture(scope="module")
def draft_lm_off():
    # a DIVERGENT draft (different init): proposals mostly reject, so
    # the correction path carries the stream
    return _build_lm(seed=7)


def _run_spec(model, draft, prompts, max_new=6, sampling=None, **kw):
    """Run one engine over `prompts` and return (token lists, stats).
    `sampling` maps the prompt index to its SamplingParams."""
    if draft is not None:
        kw.setdefault("draft_model", draft)
    eng = GenerationEngine(model, slots=2, **kw)
    with eng:
        streams = [eng.submit(p, max_new_tokens=max_new,
                              sampling=(sampling(i) if sampling
                                        else None))
                   for i, p in enumerate(prompts)]
        outs = [list(int(t) for t in s.result(timeout=180))
                for s in streams]
        snap = eng.stats()
    return outs, snap


@pytest.mark.parametrize("cache", ["on", "off"])
@pytest.mark.parametrize("gamma", [2, 4])
def test_spec_greedy_parity_bit_identical(lm, draft_lm, prompts, gamma,
                                          cache):
    """THE ISSUE 16 correctness anchor: greedy speculation is
    BIT-IDENTICAL to the non-speculative engine (== the replicated
    predict-style reference) at every gamma, prefix cache on and off —
    speculation is a pure latency optimization, never a numerics
    change."""
    refs = [reference_decode(lm, p, 6) for p in prompts]
    outs, snap = _run_spec(lm, draft_lm, prompts, max_new=6,
                           spec_gamma=gamma, prefix_cache=cache)
    assert outs == refs
    assert snap["spec"] == "on" and snap["spec_fallbacks"] == 0
    assert snap["draft_dispatches"] > 0
    assert snap["spec_proposed_tokens"] > 0
    # identical weights: the draft's argmax IS the target's argmax
    assert snap["accept_rate"] == 1.0
    assert snap["draft_kv_cache_bytes"] > 0


def test_spec_divergent_draft_correction_parity(lm, draft_lm_off,
                                                prompts):
    """A draft that mostly DISAGREES with the target still yields
    reference-exact tokens: rejected windows emit the target's
    correction token, and the stream advances one-at-a-time."""
    refs = [reference_decode(lm, p, 4) for p in prompts[:2]]
    outs, snap = _run_spec(lm, draft_lm_off, prompts[:2], max_new=4,
                           spec_gamma=4)
    assert outs == refs
    assert snap["accept_rate"] < 0.5  # divergent weights rarely agree
    assert snap["spec"] == "on" and snap["spec_fallbacks"] == 0


def test_spec_accept_collapse_demotes_to_plain(lm, draft_lm_off,
                                               prompts, monkeypatch):
    """The accept-collapse guard: a draft whose EWMA accept rate stays
    under the floor is demoted to plain decode — one serve_health
    fallback event, no failed streams, tokens still reference-exact."""
    monkeypatch.setattr(GenerationEngine,
                        "_SPEC_COLLAPSE_MIN_PROPOSED", 8)
    monkeypatch.setattr(GenerationEngine, "_SPEC_COLLAPSE_ACCEPT", 0.9)
    refs = [reference_decode(lm, p, 8) for p in prompts[:3]]
    with capture_events("serve") as events:
        outs, snap = _run_spec(lm, draft_lm_off, prompts[:3],
                               max_new=8, spec_gamma=4)
    assert outs == refs
    assert snap["spec"] == "fallback" and snap["spec_fallbacks"] == 1
    assert snap["errors"] == 0
    ev = [e for e in events if e["event"] == "serve_health"
          and e.get("component") == "speculation"]
    assert len(ev) == 1
    assert ev[0]["reason"] == "accept_collapse"
    assert ev[0]["status"] == "fallback"
    assert ev[0]["accept_ewma"] < 0.9


def test_spec_eos_and_max_new_truncate_mid_window(lm, draft_lm,
                                                  prompts):
    """EOS and max_new under speculation truncate EXACTLY like the
    plain engine, including when the stop lands mid-verify-window
    (accepted tokens past the stop are discarded, never emitted)."""
    ref = reference_decode(lm, prompts[0], 6)
    eng = GenerationEngine(lm, slots=2, draft_model=draft_lm,
                           spec_gamma=4, eos_id=int(ref[2]))
    with eng:
        out = list(int(t) for t in
                   eng.submit(prompts[0], max_new_tokens=6)
                   .result(timeout=180))
    assert out == ref[:3]  # stops at (and includes) EOS, mid-window
    # max_new that is not a multiple of the window: exact truncation
    outs, _ = _run_spec(lm, draft_lm, prompts[:2], max_new=3,
                        spec_gamma=4)
    assert outs == [reference_decode(lm, p, 3) for p in prompts[:2]]


def test_spec_adaptive_policy_parity(lm, draft_lm, prompts):
    """The adaptive gamma controller changes WHEN tokens land, never
    WHICH tokens: greedy parity holds while gamma retunes."""
    outs, snap = _run_spec(lm, draft_lm, prompts[:3], max_new=8,
                           spec_policy="adaptive", spec_gamma_max=4)
    assert outs == [reference_decode(lm, p, 8) for p in prompts[:3]]
    assert snap["spec"] == "on" and snap["draft_dispatches"] > 0
    assert snap["spec_policy"] == "adaptive"
    assert 2 <= snap["spec_gamma"] <= 4


def test_sampled_decode_deterministic_and_temp0_greedy(lm, prompts):
    """Real sampling is deterministic per (seed, request): the same
    submission replays the same tokens run over run; temperature 0
    through the sampled path IS greedy (exact one-hot, same argmax)."""
    def sp(i):
        return SamplingParams(temperature=0.8, top_k=8, top_p=0.9,
                              seed=100 + i)
    outs1, _ = _run_spec(lm, None, prompts[:3], max_new=6, sampling=sp)
    outs2, _ = _run_spec(lm, None, prompts[:3], max_new=6, sampling=sp)
    assert outs1 == outs2
    outs0, _ = _run_spec(lm, None, prompts[:3], max_new=6,
                         sampling=lambda i: SamplingParams(
                             temperature=0.0, seed=5))
    assert outs0 == [reference_decode(lm, p, 6) for p in prompts[:3]]
    # distinct seeds genuinely sample distinct continuations
    a, _ = _run_spec(lm, None, [prompts[0]], max_new=12,
                     sampling=lambda i: SamplingParams(temperature=1.5,
                                                       seed=1))
    b, _ = _run_spec(lm, None, [prompts[0]], max_new=12,
                     sampling=lambda i: SamplingParams(temperature=1.5,
                                                       seed=2))
    assert a != b


def test_spec_sampled_reproducible(lm, draft_lm_off, prompts):
    """Speculation + sampling: the rejection-sampling acceptance path
    (draft q vs target p, per-request seeded keys) replays the same
    tokens run over run."""
    def sp(i):
        return SamplingParams(temperature=0.8, seed=50 + i)
    kw = dict(max_new=6, sampling=sp, spec_gamma=2)
    outs1, snap1 = _run_spec(lm, draft_lm_off, prompts[:2], **kw)
    outs2, snap2 = _run_spec(lm, draft_lm_off, prompts[:2], **kw)
    assert outs1 == outs2
    assert snap1["spec"] == "on" and snap1["draft_dispatches"] > 0
    assert snap1["spec_fallbacks"] == 0


def test_speculative_accept_preserves_target_distribution():
    """The rejection-sampling exactness pin: tokens emitted through
    draft -> accept -> residual are distributed as the TARGET p, not
    the draft q — for the windowed kernel the engine dispatches AND
    the single-position reference sampler, with the acceptance rate
    matching sum(min(p, q))."""
    from flexflow_tpu.serving.generation.sampling import (
        speculative_accept, speculative_sample)
    V, N = 8, 40000
    rng = np.random.default_rng(0)
    p = rng.dirichlet(np.ones(V)).astype(np.float32)
    q = rng.dirichlet(np.ones(V)).astype(np.float32)
    pj, qj = jnp.asarray(p), jnp.asarray(q)
    kd, ka, kr = jax.random.split(jax.random.PRNGKey(42), 3)
    d = jax.random.categorical(kd, jnp.log(qj), shape=(N,))[:, None]
    accept_keys = jax.random.split(ka, N).reshape(N, 1, 2)
    residual_keys = jax.random.split(kr, N).reshape(N, 1, 2)
    P = jnp.broadcast_to(pj, (N, 1, V))
    Q = jnp.broadcast_to(qj, (N, 1, V))
    n_acc, out = speculative_accept(d, P, Q, accept_keys,
                                    residual_keys)
    emp = np.bincount(np.asarray(out)[:, 0], minlength=V) / N
    assert 0.5 * np.abs(emp - p).sum() < 0.02          # TV distance
    assert abs(float(jnp.mean(n_acc))
               - float(np.minimum(p, q).sum())) < 0.02
    # emitting from q would be FAR off: the test can actually fail
    assert 0.5 * np.abs(p - q).sum() > 0.1
    ref = np.asarray(speculative_sample(jax.random.PRNGKey(7), pj, qj,
                                        N))
    emp_ref = np.bincount(ref, minlength=V) / N
    assert 0.5 * np.abs(emp_ref - p).sum() < 0.02


def test_sharded_spec_matches_reference(tmp_path, lm, draft_lm,
                                        prompts):
    """Greedy speculation parity holds on the strategy-sharded engine
    too: TP target + replicated co-hosted draft, tokens identical to
    the replicated reference."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    pb = tmp_path / "gen_tp.pb"
    _write_tp_strategy(pb)
    m2 = _build_lm()  # same seed -> same init values as `lm`
    eng = GenerationEngine.from_strategy(m2, str(pb), slots=4,
                                         draft_model=draft_lm,
                                         spec_gamma=2)
    with eng:
        outs = [list(int(t) for t in
                     eng.submit(p, max_new_tokens=6).result(timeout=180))
                for p in prompts[:3]]
        snap = eng.stats()
    assert outs == [reference_decode(lm, p, 6) for p in prompts[:3]]
    assert snap["draft_dispatches"] > 0
    assert snap["spec_fallbacks"] == 0


def test_gen_stats_carry_spec_fields(lm, draft_lm, prompts):
    """gen_stats/stats() gain the ISSUE 16 fields from the ONE metrics
    plane; a plain engine reports spec='off' with zero spec traffic."""
    _, snap = _run_spec(lm, draft_lm, prompts[:1], max_new=4,
                        spec_gamma=2)
    for key in ("spec", "spec_gamma", "spec_policy",
                "draft_kv_cache_bytes", "draft_dispatches",
                "spec_proposed_tokens", "spec_accepted_tokens",
                "accept_rate", "spec_fallbacks"):
        assert key in snap, key
    assert snap["spec"] == "on" and snap["spec_gamma"] == 2
    assert snap["spec_policy"] == "fixed"
    assert snap["draft_kv_cache_bytes"] > 0
    _, snap0 = _run_spec(lm, None, prompts[:1], max_new=4)
    assert snap0["spec"] == "off"
    assert snap0["draft_dispatches"] == 0
    assert snap0["draft_kv_cache_bytes"] == 0


def test_spec_config_validation(lm, draft_lm):
    with pytest.raises(ValueError, match=">= 2"):
        GenerationEngine(lm, slots=2, draft_model=draft_lm,
                         spec_gamma=1)
    with pytest.raises(ValueError, match="spec_policy"):
        GenerationEngine(lm, slots=2, draft_model=draft_lm,
                         spec_gamma=2, spec_policy="bogus")
    with pytest.raises(ValueError, match="speculation is off"):
        GenerationEngine(lm, slots=2, draft_model=draft_lm,
                         spec_gamma=0)
    with pytest.raises(ValueError, match="spec_gamma_max"):
        GenerationEngine(lm, slots=2, draft_model=draft_lm,
                         spec_gamma=4, spec_gamma_max=2)
    # an uncompiled draft is caught before any pool is allocated
    from flexflow_tpu.models import build_transformer_lm
    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=0)
    fresh = build_transformer_lm(cfg, num_layers=1, d_model=32,
                                 num_heads=2, d_ff=64, seq_len=SEQ,
                                 vocab_size=VOCAB)[0]
    with pytest.raises(AssertionError, match="draft model"):
        GenerationEngine(lm, slots=2, draft_model=fresh, spec_gamma=2)
    # LSTM graphs cannot speculate (no rollback-free attention cache)
    from flexflow_tpu.models import build_lstm_lm
    cfg2 = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=5)
    lstm = build_lstm_lm(cfg2, vocab_size=VOCAB, embed_dim=24,
                         hidden_dim=24, num_layers=1, seq_len=SEQ)[0]
    lstm.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    lstm.init_layers(seed=5)
    with pytest.raises(ValueError, match="attention"):
        GenerationEngine(lstm, slots=2, draft_model=draft_lm,
                         spec_gamma=2)
    # SamplingParams validates its ranges up front
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)


# ---------------------------------------------------------------------
# the stored form of the pool: no serving program copies it (ISSUE 25)
# ---------------------------------------------------------------------
# a pool of 12 pages on 4 slots x 2 pages: the view a decode gathers
# (8 pages) is not pool-sized, so only a copy of the POOL has a pool
# leaf's element count
_POOL_PAGES = 12
_COMPILE_LIMIT_S = 240


def _built_decoder(model, num_pages=_POOL_PAGES):
    dec = GraphDecoder.for_model(model, 4, SEQ, num_pages=num_pages)
    dec.decode_fn()
    dec.prefill_fn(16)
    dec.verify_fn(4)
    dec.draft_fn(4)
    return dec


@contextlib.contextmanager
def _no_compilation_cache():
    """An executable compiled for a described chip can be written to the
    persistent cache but not read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _within(seconds, fn):
    """``fn()`` on a thread of its own, failed (not waited for) past
    its time limit: a compile that hangs must not hold the suite."""
    import threading
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        pytest.fail(f"still compiling after {seconds} s")
    if "err" in box:
        raise box["err"]
    return box["out"]


def test_count_copies_counts_copies_that_pass_over_memory():
    """The reading behind pool_copies(): by element count; a copy of an
    unfused computation or the ROOT of a fusion one calls counts, a
    copy nested inside another fusion (how a convolution reads its
    operand) does not, a copy of another size does not."""
    from flexflow_tpu.serving.generation.decoder import count_copies
    text = """HloModule jit_decode

%nested (p: bf16[8,16,128]) -> bf16[8,16,128] {
  %p = bf16[8,16,128]{2,1,0} parameter(0)
  ROOT %copy.1 = bf16[8,16,128]{0,2,1} copy(%p)
}

%conv (a: bf16[8,16,128]) -> f32[8,16] {
  %a = bf16[8,16,128]{2,1,0} parameter(0)
  %fusion.9 = bf16[8,16,128]{0,2,1} fusion(%a), kind=kLoop, calls=%nested
  ROOT %r = f32[8,16]{1,0} convolution(%fusion.9, %fusion.9)
}

%transposer (q: bf16[8,16,128]) -> bf16[8,16,2,64] {
  %q = bf16[8,16,128]{2,1,0} parameter(0)
  %copy.5 = bf16[8,16,128]{2,1,0} copy(%q)
  ROOT %copy.2 = bf16[8,16,2,64]{3,1,2,0:T(8,128)(2,1)} copy(%copy.5)
}

ENTRY %main (k: bf16[8,16,128], w: f32[128,96]) -> f32[8,16] {
  %k = bf16[8,16,128]{2,1,0} parameter(0)
  %w = f32[128,96]{1,0} parameter(1)
  %copy.3 = bf16[8,16,128]{1,2,0} copy(%k), metadata={op_name="x"}
  %copy.4 = f32[128,96]{0,1} copy(%w)
  %copy-start.1 = (bf16[8,16,128]{2,1,0}, bf16[8,16,128]{2,1,0}, u32[]) copy-start(%k)
  %fusion.1 = bf16[8,16,2,64]{3,1,2,0} fusion(%k), kind=kLoop, calls=%transposer
  ROOT %fusion.2 = f32[8,16]{1,0} fusion(%copy.3), kind=kOutput, calls=%conv
}
"""
    # copy.3, copy-start.1 and the ROOT of %transposer; not copy.1
    # (nested), copy.5 (inside a fusion, not its root), copy.4 (a weight)
    assert count_copies(text, [8 * 16 * 128]) \
        == {"count": 3, "bytes": 3 * 8 * 16 * 128 * 2}
    assert count_copies(text, [128 * 96]) == {"count": 1,
                                              "bytes": 128 * 96 * 4}
    assert count_copies(text, [7]) == {"count": 0, "bytes": 0}


def test_no_serving_program_copies_the_pool_cpu(lm_lane_dense):
    """decode, a prefill bucket, verify and the draft scan, compiled
    for the CPU: no copy the size of a pool leaf — the scatter updates
    the donated pool in place and the gather reads it where it lies."""
    dec = _built_decoder(lm_lane_dense)
    leaf = dec.layout["attention_0"]["shapes"]["k"]
    assert leaf == (_POOL_PAGES, 16, 128)
    got = _within(_COMPILE_LIMIT_S, dec.pool_copies)
    assert set(got) == {"jit_decode", "jit_prefill.16", "jit_verify.4",
                        "jit_draft.4"}
    assert all(v == {"count": 0, "bytes": 0} for v in got.values()), got


@pytest.fixture(scope="module")
def v5e_device():
    """One chip of a v5e that is only DESCRIBED: the TPU's compiler is
    installed here and compiles for it with no chip attached.  Made
    inside a fixture, never while a module is imported (one process at
    a time may load the TPU's library)."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def test_no_serving_program_copies_the_pool_tpu(v5e_device):
    """The same four programs in bf16, as served, compiled by the TPU's
    compiler, where the (pages, page, heads, head_dim) form read 6
    pool-sized copies a layer in each (8 in a decode whose gathered
    view is pool-sized too; 2 x 64 minor fills no (8, 128) tile, so
    scatter, gather and einsum each wanted the pool another way): the
    folded form reads none.  The pool is sized like a deployment's
    (8192 pages, 33.5 MB a leaf: only lowered here, never allocated) —
    one of a few pages the compiler moves whole into fast memory and
    back, which is a copy, and counted."""
    dec = _built_decoder(
        _build_lm(slots=4, num_layers=1, d_model=128, d_ff=192,
                  compute_dtype="bfloat16"), num_pages=8192)
    with _no_compilation_cache():
        got = _within(_COMPILE_LIMIT_S,
                      lambda: dec.pool_copies(device=v5e_device))
    assert set(got) == {"jit_decode", "jit_prefill.16", "jit_verify.4",
                        "jit_draft.4"}
    assert all(v == {"count": 0, "bytes": 0} for v in got.values()), got


@pytest.mark.parametrize("kernel,clean", [("owned", True),
                                          ("library", False)])
def test_train_step_holds_no_flash_wrapper_tpu(v5e_device, monkeypatch,
                                               kernel, clean):
    """The TRAIN step of a two-layer encoder (head size 64, s = 512, as
    the benchmark's train cell) compiled by the TPU's compiler: with the
    repo's own flash kernel no instruction under an attention scope
    widens a row statistic to (n, h, s, >= 128) f32 or copies a
    (n, s, h, d) array into another layout; with jax's library kernel
    (the same shape, the owned kernel refused) both are there, so the
    reader reads.  Here and not in tests/test_transformer.py: every
    described-chip compile of the suite lives in this one file, whose
    worker alone loads the TPU's library."""
    from flexflow_tpu.models.transformer import build_transformer
    from flexflow_tpu.obs.device_ops import attention_wrapper_ops
    from flexflow_tpu.ops import attention as attn_mod, flash_kernel
    from jax.sharding import SingleDeviceSharding

    cfg = ff.FFConfig.parse_args(["-b", "4", "-ll:tpu", "1"])
    cfg.compute_dtype = "bfloat16"
    model, _, logits = build_transformer(
        cfg, num_layers=2, d_model=256, num_heads=4, d_ff=512, seq_len=512,
        vocab_size=128, num_classes=2)
    model.compile(ff.AdamOptimizer(alpha=1e-4),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.METRICS_ACCURACY], final_tensor=logits)
    model.init_layers(seed=0)
    # steer the code that asks for the backend, as the chip would answer
    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash_kernel, "_interpret", lambda: False)
    if kernel == "library":
        monkeypatch.setattr(flash_kernel, "supported", lambda *a: False)
    chip = SingleDeviceSharding(v5e_device)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    batch = (jax.ShapeDtypeStruct((4, 512), jnp.int32, sharding=chip),
             jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=chip))
    def compiled_text():
        # on this thread: the suite's float32 matmul default is nothing
        # jax's kernel lowers
        with jax.default_matmul_precision("default"):
            return model._train_step.lower(
                described(model._params), described(model._opt_state),
                batch, model._step).compile().as_text()

    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S, compiled_text)
    assert model.attention_kernels()[kernel] == 2
    attention = [op.name for op in model.layers
                 if isinstance(op, MultiHeadAttention)]
    found = attention_wrapper_ops(text, attention)
    assert (found == []) if clean else len(found) >= 8, found
    assert ("flash_mha_bwd_fused" in text) == clean
    assert "flash_attention_fwd" in text or not clean


# ---------------------------------------------------------------------
# decode attention reads the pages where they lie (ISSUE 30)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 1e-2)])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_paged_decode_kernel_matches_gathered_decode(head_dim, dtype, tol):
    """The kernel (Pallas interpret mode on the CPU) against
    ``_decode_attention`` on the gathered, unfolded view: slots at
    position 0, a page's last row, a page's first row, inside the SECOND
    copy group (640 positions a slot, 512 a group) and ``max_seq - 1``,
    and one slot at the ``no_page`` sentinel, which reads zero.  Every
    page beyond a slot's position — stale table entries, another
    stream's pages by now — is NaN in the pools the kernel gets: read,
    it would show.  Tolerance: f32 differs by the order of the online
    softmax's sums (1e-5); bf16 by one rounding of an unnormalised
    weight to 8 bits where the gathered path rounds the normalised one
    (2 ** -8 a term on values of order 1: 1e-2)."""
    from flexflow_tpu.ops.attention import _decode_attention
    from flexflow_tpu.ops.paged_decode_kernel import paged_decode_attention

    heads, page, pps = 2, 16, 40
    e, max_seq = heads * head_dim, page * pps
    pos = np.array([0, page - 1, page, 530, 77, max_seq - 1], np.int32)
    slots, idle = len(pos), 4
    rng = np.random.default_rng(head_dim)
    num_pages = slots * pps + 3
    table = rng.permutation(num_pages)[:slots * pps].reshape(
        slots, pps).astype(np.int32)
    write_pages = table[np.arange(slots), pos // page]
    write_pages[idle] = num_pages
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
               for shape in ((slots, e), (num_pages, page, e),
                             (num_pages, page, e)))

    def view(pool):
        return jnp.take(pool, table, axis=0).reshape(slots, max_seq,
                                                     heads, head_dim)

    scale = 1.0 / np.sqrt(head_dim)
    want = _decode_attention(q.reshape(slots, 1, heads, head_dim), view(k),
                             view(v), jnp.asarray(pos), scale)
    stale = np.ones(num_pages, bool)
    for i in range(slots):
        if i != idle:
            stale[table[i, :pos[i] // page + 1]] = False
    poison = jnp.asarray(stale)[:, None, None]
    got = paged_decode_attention(
        q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v),
        jnp.asarray(table), jnp.asarray(pos), jnp.asarray(write_pages),
        heads, scale)
    assert got.dtype == jnp.float32 and got.shape == (slots, e)
    decoding = np.arange(slots) != idle
    np.testing.assert_allclose(
        np.asarray(got)[decoding],
        np.asarray(want).reshape(slots, e)[decoding], rtol=tol, atol=tol)
    assert np.all(np.asarray(got)[idle] == 0.0)


@pytest.mark.parametrize("why,args", [
    ("the serve cell", ("tpu", "bfloat16", 12, 64, 16, False)),
    ("f32, one head a tile", ("tpu", "float32", 8, 128, 8, False)),
    ("four heads a tile, a page of two chunks", ("tpu", "bfloat16", 8, 32,
                                                  256, False)),
    ("not a TPU", ("cpu", "bfloat16", 12, 64, 16, False)),
    ("a sharded pool", ("tpu", "bfloat16", 12, 64, 16, True)),
    ("a dtype the MXU does not take", ("tpu", "float16", 12, 64, 16, False)),
    ("rows that fill no lane tile", ("tpu", "float32", 2, 16, 8, False)),
    ("a head that straddles tiles", ("tpu", "float32", 4, 96, 8, False)),
    ("a bf16 page of half a tile", ("tpu", "bfloat16", 12, 64, 8, False)),
    ("a page that tiles no chunk", ("tpu", "float32", 12, 64, 24, False)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_paged_decode_supported_reads_what_the_code_can_see(why, args):
    from flexflow_tpu.ops.paged_decode_kernel import supported
    backend, dtype, *rest = args
    assert supported(backend, jnp.dtype(dtype), *rest) == (why in (
        "the serve cell", "f32, one head a tile",
        "four heads a tile, a page of two chunks")), why


def _view_sized(text, elements):
    """The instructions of a compiled module's text whose result holds
    ``elements`` elements."""
    found = []
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%\S+\s*=\s*\(?\s*\w+"
                         r"\[(?P<dims>[\d,]*)\]", text, re.M):
        if int(np.prod([int(d) for d in m.group("dims").split(",") if d]
                       or [1])) == elements:
            found.append(m.group(0).strip())
    return found


def test_decode_reads_the_pool_in_place_tpu(v5e_device, monkeypatch):
    """``jit_decode`` and the ``jit_draft.4`` scan in bf16, compiled by
    the TPU's compiler with the backend steered as the chip would answer:
    each holds the ``paged_decode_attention`` kernel, NO instruction of
    the gathered view's element count (slots x pages_per_slot x page x
    heads x head_dim: the gather and its unfold are gone) and no
    pool-sized copy; the prompt chunk and the verify window gather as
    before (one slot's view, a window's: docs/serving.md says why).  5
    slots and d_ff 192, so that no weight has the view's element count;
    the same programs traced WITHOUT the steering hold the view, so the
    reader reads.  The counter says which core was traced, on the decoder and
    in an engine's ``stats()``."""
    from flexflow_tpu.ops import attention as attn_mod

    slots, num_pages = 5, 8192
    model = _build_lm(slots=slots, num_layers=1, d_model=128, d_ff=192,
                      compute_dtype="bfloat16")

    def built(dec):
        dec.decode_fn(), dec.prefill_fn(16), dec.verify_fn(4), dec.draft_fn(4)
        return dec

    def compiled_texts(dec):
        with _no_compilation_cache():
            return _within(_COMPILE_LIMIT_S, lambda: {
                key: fn.lower(*args).compile().as_text()
                for key, _, fn, args in dec._program_specs(v5e_device)})

    # a decoder of its own: a program is traced once, as the backend
    # answered then
    control = built(GraphDecoder(model, slots, SEQ, num_pages=num_pages))
    view = slots * control.pages_per_slot * control.page_size * 128
    gathered = compiled_texts(control)
    assert control.decode_attention() == {"paged": 0, "gathered": 1}
    assert all(_view_sized(gathered[key], view)
               for key in ("jit_decode", "jit_draft.4", "jit_verify.4"))
    assert not any("paged_decode_attention" in t for t in gathered.values())

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    dec = built(GraphDecoder.for_model(model, slots, SEQ,
                                       num_pages=num_pages))
    paged = compiled_texts(dec)
    assert dec.decode_attention() == {"paged": 1, "gathered": 0}
    for key in ("jit_decode", "jit_draft.4"):
        assert "paged_decode_attention" in paged[key], key
        assert _view_sized(paged[key], view) == [], key
    for key in ("jit_prefill.16", "jit_verify.4"):
        assert "paged_decode_attention" not in paged[key], key
    assert _view_sized(paged["jit_verify.4"], view)
    with _no_compilation_cache():
        got = _within(_COMPILE_LIMIT_S,
                      lambda: dec.pool_copies(device=v5e_device))
    assert set(got) == set(paged)
    assert all(v == {"count": 0, "bytes": 0} for v in got.values()), got
    # an engine on this geometry shares the decoder; never started here
    eng = GenerationEngine(model, slots=slots, num_pages=num_pages)
    assert eng.stats()["decode_attention"] == {"paged": 1, "gathered": 0}


def test_gen_stats_carry_decode_attention(lm, prompts):
    """On the CPU every attention op of the graph decodes over the
    gathered view, and ``stats()`` says so once a token step is
    traced."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=3)
    with eng:
        eng.submit(prompts[0]).result(timeout=120)
        assert eng.stats()["decode_attention"] == {"paged": 0,
                                                   "gathered": 2}


# ---------------------------------------------------------------------
# ISSUE 34: the decode loop runs one step ahead of the host
# ---------------------------------------------------------------------
def _serve(model, reqs, ahead, eos_id=None, **kw):
    """Serve ``reqs`` (``(prompt, max_new, sampling)``) on one engine:
    through its own loop (one step ahead) or one synchronous boundary
    at a time (``dispatch_pending``, the fleet's entry).  Returns the
    token lists and ``stats()``."""
    eng = GenerationEngine(model, slots=2, eos_id=eos_id, **kw)
    if ahead:
        eng.start()
    else:
        eng.begin_external_dispatch()
    try:
        streams = [eng.submit(p, max_new_tokens=n, sampling=sp)
                   for p, n, sp in reqs]
        if not ahead:
            for _ in range(500):
                if not eng.has_pending:
                    break
                eng.dispatch_pending()
                # the fleet is never owed tokens: nothing stays in flight
                assert eng._inflight is None and not eng._cur
        outs = [[int(t) for t in s.result(timeout=120)] for s in streams]
        return outs, eng.stats()
    finally:
        eng.stop()


def _shared_prefix_prompts():
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, VOCAB, 20).astype(np.int32)
    return [np.concatenate([prefix,
                            rng.integers(1, VOCAB, 3).astype(np.int32)])
            for _ in range(4)]


@pytest.mark.parametrize("case", ["greedy", "sampled", "eos", "max_new_1",
                                  "chunked", "prefix_hit"])
def test_one_step_ahead_serves_the_synchronous_boundarys_tokens(
        lm, prompts, case):
    """Tokens are bit-identical with the pipeline running and with
    nothing in flight, and equal the predict-style reference wherever
    decoding is greedy; what the pipeline did shows in
    ``stats()["decode_pipeline"]`` alone."""
    new, eos, kw, sp = 6, None, {}, (lambda i: None)
    ps = prompts[:5]
    if case == "sampled":
        def sp(i):
            return SamplingParams(temperature=0.8, top_k=8, top_p=0.9,
                                  seed=40 + i)
    elif case == "eos":
        # the third token of the first stream ends every stream it
        # occurs in, mid-batch
        eos = reference_decode(lm, ps[0], new)[2]
    elif case == "max_new_1":
        new = 1
    elif case == "chunked":
        kw = {"prefill_chunk": 3}
    elif case == "prefix_hit":
        ps, new = _shared_prefix_prompts(), 5
    reqs = [(p, new, sp(i)) for i, p in enumerate(ps)]
    ahead, snap = _serve(lm, reqs, True, eos_id=eos, **kw)
    sync, snap0 = _serve(lm, reqs, False, eos_id=eos, **kw)
    assert ahead == sync
    refs = [reference_decode(lm, p, new) for p in ps]
    ends = [r.index(eos) if eos in r else None for r in refs]
    if case != "sampled":
        assert ahead == [r if k is None else r[:k + 1]
                         for r, k in zip(refs, ends)]
    pipe, pipe0 = snap["decode_pipeline"], snap0["decode_pipeline"]
    # nothing in flight: never ahead, every step fetched where it ran
    assert pipe0["ahead"] == 0 and pipe0["dropped_tokens"] == 0
    steps = pipe0["drained"].get("external", 0)
    assert set(pipe0["drained"]) <= {"external"}
    if case == "max_new_1":
        assert steps == 0 and pipe["ahead"] == 0     # no token step at all
    else:
        assert 0 < pipe["ahead"] < steps + len(ps)
        assert "external" not in pipe["drained"]
    if case == "eos":
        # found one step late: the step dispatched meanwhile computed a
        # token for a stream that had ended (two where the FIRST token
        # was the EOS), unless that token was its last by count anyway
        owed = sum(min(new - (k + 1), 2 if k == 0 else 1)
                   for k in ends if k is not None)
        assert owed > 0 and pipe["dropped_tokens"] == owed
        assert snap["tokens"] == sum(len(o) for o in ahead)
    else:
        assert pipe["dropped_tokens"] == 0
    if case == "prefix_hit":
        assert snap["prefix_hit_tokens"] > 0
        assert snap["prefix_hit_tokens"] == snap0["prefix_hit_tokens"]
    if case == "chunked":
        assert snap["prefill_chunks"] == snap0["prefill_chunks"] > len(ps)


def test_pages_freed_by_count_reach_the_next_join_behind_their_last_reader(
        lm, prompts, monkeypatch):
    """The page-order invariant: a slot that retires by count frees its
    pages when its last step is DISPATCHED, and the next boundary's join
    takes them.  Every freed page is NaN on the device from its release
    until it is handed out again (as PR 30's kernel test poisons what a
    slot must not read): a release ordered before the page's last reader,
    or a later step reading through a stale table row, would serve NaN's
    argmax.  Two pages for two slots: every join after the second takes
    a page a retiring slot has just freed."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=5, num_pages=2,
                           prefix_cache="off")
    poisoned, reused = set(), []
    release, alloc = eng._release_slot, eng._alloc_page

    def fill(pages, value):
        idx = np.asarray(sorted(pages), np.int32)
        eng._caches = jax.tree_util.tree_map(
            lambda a: a.at[idx].set(value), eng._caches)

    def release_and_poison(slot, st):
        pages = list(st.pages)
        release(slot, st)
        freed = {p for p in pages if eng._pool.refcount(p) == 0}
        if freed:
            fill(freed, jnp.nan)
            poisoned.update(freed)

    def alloc_and_clear(*a):
        pg = alloc(*a)
        if pg in poisoned:
            poisoned.discard(pg)
            reused.append(pg)
            fill({pg}, 0)
        return pg

    monkeypatch.setattr(eng, "_release_slot", release_and_poison)
    monkeypatch.setattr(eng, "_alloc_page", alloc_and_clear)
    with eng:
        streams = [eng.submit(p) for p in prompts]
        outs = [[int(t) for t in s.result(timeout=120)] for s in streams]
        snap = eng.stats()
    assert outs == [reference_decode(lm, p, 5) for p in prompts]
    assert len(reused) >= len(prompts) - 2
    assert snap["decode_pipeline"]["ahead"] > 0
    assert snap["errors"] == 0 and eng._pool.pages_in_use == 0


def test_decode_pipeline_counter_plain_speculating_and_drained(
        lm, draft_lm, prompts):
    """``stats()["decode_pipeline"]``: a plain engine runs ahead; a
    speculating one needs the accept counts on the host and never does;
    the reading outlives ``drain()``."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=6)
    eng.start()
    outs = [[int(t) for t in eng.submit(p).result(timeout=120)]
            for p in prompts[:2]]
    live = eng.stats()["decode_pipeline"]
    after = eng.drain(timeout=60)["decode_pipeline"]
    assert outs == [reference_decode(lm, p, 6) for p in prompts[:2]]
    # one stream at a time: five token steps each, every one but the
    # first dispatched before its predecessor's tokens were fetched, and
    # the pipeline ran empty once a stream, when its batch did
    assert live == after == {"ahead": 8, "drained": {"idle": 2},
                             "dropped_tokens": 0}
    assert eng._inflight is None
    spec, snap = _run_spec(lm, draft_lm, prompts[:3], max_new=6,
                           spec_gamma=2)
    assert spec == [reference_decode(lm, p, 6) for p in prompts[:3]]
    assert snap["spec"] == "on"
    assert snap["decode_pipeline"] == {"ahead": 0, "drained": {},
                                       "dropped_tokens": 0}


def test_warm_engine_serves_without_another_lowering(lm, prompts):
    """``_warmup`` calls the token step the way the loop does (its
    tokens spliced on the device), so serving adds no entry to the
    step's or the splice's cache: no trace and no compile inside a
    window."""
    from flexflow_tpu.serving.generation import engine as engine_mod
    eng = GenerationEngine(lm, slots=2, max_new_tokens=6)
    with eng:
        step, splice = eng._decoder.decode_fn(), engine_mod._splice_tokens
        warm = (step._cache_size(), splice._cache_size())
        streams = [eng.submit(p) for p in prompts[:4]]
        for s in streams:
            s.result(timeout=120)
        assert (step._cache_size(), splice._cache_size()) == warm
        assert eng.stats()["decode_pipeline"]["ahead"] > 0


def test_dispatch_error_with_a_step_in_flight_fails_each_stream_once(
        lm, prompts, monkeypatch):
    """A token step that raises while the one before it is still in
    flight fails the streams of BOTH (the cache it consumed fed them
    all), each once — the one that had retired by count and was only
    owed its last token too — and the engine serves the next prompt."""
    eng = GenerationEngine(lm, slots=2, prefix_cache="off")
    decode_fn = eng._decoder.decode_fn
    calls = {"n": 0}

    def hooked():
        fn = decode_fn()

        def decode(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("injected: third token step")
            return fn(*a, **kw)

        return decode

    monkeypatch.setattr(eng._decoder, "decode_fn", hooked)
    # both queued before the loop starts: `short` joins at the first
    # boundary and retires by count when the second step is dispatched,
    # `long` joins at the second; the third step raises with the second
    # still in flight
    short = eng.submit(prompts[0], max_new_tokens=3)
    long = eng.submit(prompts[1], max_new_tokens=12)
    with capture_events("serve") as events:
        eng.start(warmup=False)     # no warm-up: the loop's steps alone count
        try:
            for s in (short, long):
                with pytest.raises(RuntimeError, match="third token step"):
                    s.result(timeout=120)
            assert len(short.tokens_so_far()) == 2    # the third never came
            late = eng.submit(prompts[2], max_new_tokens=4)
            assert ([int(t) for t in late.result(timeout=120)]
                    == reference_decode(lm, prompts[2], 4))
            snap = eng.stats()
        finally:
            eng.stop()
    errors = [e for e in events if e.get("event") == "gen_decode_error"]
    assert len(errors) == 1 and errors[0]["failed_streams"] == 2
    assert snap["errors"] == 2 and snap["requests"] == 1
    assert eng._pool.pages_in_use == 0 and eng._inflight is None


def test_engine_stats_carry_pool_copies_once_asked(lm, draft_lm, prompts):
    """pool_copies is ON DEMAND: absent from stats() until asked, then
    the target's programs by name and the draft's under draft/."""
    eng = GenerationEngine(lm, slots=2, max_new_tokens=3,
                           draft_model=draft_lm, spec_gamma=2,
                           num_pages=6)
    with eng:
        eng.submit(prompts[0]).result(timeout=120)
        assert "pool_copies" not in eng.stats()
        got = eng.pool_copies()
        assert eng.stats()["pool_copies"] == got
    assert {"jit_decode", "jit_verify.2", "draft/jit_draft.2"} <= set(got)
    assert {f"jit_prefill.{b}" for b in eng._decoder.buckets} <= set(got)
    assert all(v["count"] == 0 for v in got.values()), got


# ---------------------------------------------------------------------
# grouped heads, rotary positions, a window with rows of its own, and a
# dropless MoE behind the serving contract (ISSUE 36)
# ---------------------------------------------------------------------
_DEC_LAYERS = [
    {"attention": "full_attention", "heads": 6, "mlp": "dense"},
    {"attention": "sliding_attention", "heads": 8, "mlp": "sparse"},
    {"attention": "sliding_attention", "heads": 8, "mlp": "sparse"},
    {"attention": "sliding_attention", "heads": 8, "mlp": "sparse"},
    {"attention": "full_attention", "heads": 6, "mlp": "sparse"}]
_DEC_ROPE = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                       "factor": 64, "original_max_position_embeddings": 16,
                       "beta_slow": 1, "beta_fast": 64,
                       "attention_factor": 1.4158883083359672,
                       "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
_DEC_SEQ, _DEC_WINDOW, _DEC_CHUNK = 64, 8, 8


def _build_decoder_lm(chunk=_DEC_CHUNK, seed=0, weights=True):
    """The pre-norm decoder at a size that keeps every kind: 2 key/value
    heads under 6 and 8 query heads, head 16, window 8, 8 experts top-2
    beside a shared one, layer 0 dense, 5 layers; float32."""
    from flexflow_tpu.models import build_decoder_lm
    cfg = ff.FFConfig(batch_size=2, compute_dtype="float32", seed=seed)
    cfg.serve_gen_slots = 2
    cfg.serve_gen_max_seq = _DEC_SEQ
    cfg.serve_prefill_chunk = chunk
    model = build_decoder_lm(
        cfg, _DEC_LAYERS, d_model=32, head_dim=16, num_kv_heads=2, d_ff=64,
        vocab_size=VOCAB, seq_len=_DEC_SEQ, window=_DEC_WINDOW,
        rope=_DEC_ROPE, gate=True,
        moe={"num_experts": 8, "k": 2, "d_ff": 16, "shared_d_ff": 16,
             "routed_scale": 2.5})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    if weights:
        model.init_layers(seed=seed)
    return model


_LAT_SEQ, _LAT_CHUNK = 64, 8
_LAT = {"q_rank": 24, "kv_rank": 32, "nope_dim": 16, "rope_dim": 8,
        "v_dim": 16, "rope_theta": 25.6e6}
_LAT_LAYERS = [{"attention": "latent_attention", "heads": 4, "mlp": "dense"},
               {"attention": "latent_attention", "heads": 4,
                "mlp": "sparse"}]


def _build_latent_lm(chunk=_LAT_CHUNK, seed=0, weights=True):
    """The sandwich-norm decoder with LATENT attention at a small size:
    hidden 64, 4 heads of nope 16 / rope 8 / v 16 over one row of 32 + 8
    values, q rank 24, a dense layer then a sparse one whose sigmoid router
    scores 16 experts top-4 and whose chip holds 8; float32."""
    from flexflow_tpu.models import build_decoder_lm
    cfg = ff.FFConfig(batch_size=2, compute_dtype="float32", seed=seed)
    cfg.serve_gen_slots = 2
    cfg.serve_gen_max_seq = _LAT_SEQ
    cfg.serve_prefill_chunk = chunk
    model = build_decoder_lm(
        cfg, _LAT_LAYERS, d_model=64, head_dim=0, num_kv_heads=0, d_ff=128,
        vocab_size=VOCAB, seq_len=_LAT_SEQ, rms_eps=1e-5, sandwich=True,
        latent=_LAT,
        moe={"num_experts": 16, "k": 4, "d_ff": 16, "shared_d_ff": 16,
             "routed_scale": 2.5, "scoring": "sigmoid", "held": (0, 8)})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    if weights:
        model.init_layers(seed=seed)
    return model


@pytest.fixture(scope="module")
def decoder_lm():
    return _build_decoder_lm()


def test_windowed_graph_serves_what_its_forward_computes(decoder_lm):
    """Prefill in chunks of 8 then decoding through the engine's cache
    against the graph's own full forward at every served position
    (float32, so bit-equal tokens): contexts shorter than the window, a
    chunk that straddles it (13 = 8 + 5), contexts that pass it several
    times over (30 + 12 positions against a window of 8 in a ring of 16
    rows), two streams at once.  The windowed entries hold ``window +
    chunk`` rows a slot and no page of the pool; the pool's ids index the
    two full layers only."""
    model = decoder_lm
    rng = np.random.default_rng(36)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (5, 8, 13, 23, 30)]
    eng = GenerationEngine(model, slots=2)
    dec = eng._decoder
    assert sorted(dec.windowed) == ["attention_1", "attention_2",
                                    "attention_3"]
    rows = _DEC_WINDOW + _DEC_CHUNK
    for name, ent in dec.windowed.items():
        assert ent["rows"] == rows and ent["window"] == _DEC_WINDOW
        assert ent["shapes"]["k"] == (2, rows // 16, 16, 2 * 16), ent
    assert dec.buckets == (2, 4, 8)         # no chunk longer than sized for
    plan = eng.kv_plan
    assert plan["window_rows"] == rows
    assert plan["window_bytes"] == 3 * 2 * 2 * rows * 32 * 4    # K+V, f32
    assert plan["page_bytes"] == 2 * 2 * 16 * 32 * 4            # 2 layers
    assert plan["total_bytes"] == plan["pool_bytes"] \
        + plan["window_bytes"] + plan["state_bytes"]
    with eng:
        streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
        outs = [[int(t) for t in s.result(timeout=300)] for s in streams]
        snap = eng.stats()
        caches = eng._caches
    for p, out in zip(prompts, outs):
        assert out == reference_decode(model, p, 12, _DEC_SEQ), len(p)
    got = sum(int(np.prod(a.shape)) * a.dtype.itemsize
              for name, sub in caches.items() for a in sub.values())
    assert got == plan["total_bytes"]
    kv = snap["kv_pages"]
    assert kv["windowed"]["rows_per_slot"] == rows
    assert kv["windowed"]["entries"] == 3 and kv["full"]["entries"] == 2
    assert kv["full"]["in_use"] == 0 and kv["full"]["high_water"] > 0
    assert snap["prefix_cache"] == "off"
    assert "windowed cache entry: attention_1" in kv["windowed"]["refused"]
    assert snap["decode_attention"] == {
        "paged": 0, "gathered": 5, "windowed": {"paged": 0, "gathered": 3}}
    moe = dict(snap["moe"])
    # every sparse op in every program traced (three chunk buckets and
    # the token step), the library's on the CPU
    assert moe.pop("grouped_product") == {"rows": 0, "library": 16}
    assert sorted(moe) == ["moe_1", "moe_2", "moe_3", "moe_4"]
    served = sum(len(p) for p in prompts) + 5 * 11 + len(dec.buckets)
    for m in moe.values():      # 2 choices a live token, the warm-up's
        assert m["assignments"] == 2 * served, m     # 1-token chunks too
        assert len(m["load"]) == 8 and m["token_steps"] > 0
        assert 0.0 <= m["untouched_share"] < 1.0
        assert m["load_max_over_mean"] >= 1.0


def _moe_ops(snap):
    """``stats()["moe"]`` without the key that is not an op's."""
    return {n: m for n, m in snap["moe"].items() if n != "grouped_product"}


def test_grouped_product_counts_every_sparse_op_of_every_program():
    """``stats()["moe"]["grouped_product"]``: which grouped product each
    sparse op took in each serving program traced, noted by the op at
    trace time.  On the CPU the library's everywhere; it counts chunk
    programs and the token step alike (a decoder of its own, so only what
    is traced here: one chunk bucket, then the token step; a model of its
    own, since an op notes a program when it is TRACED), grows as programs
    are traced, and is read from host memory only: with every device
    buffer of the engine out of reach it still answers."""
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    decoder_lm = _build_decoder_lm(seed=37)
    ops = [op for op in decoder_lm.layers if hasattr(op, "grouped_product")]
    assert [op.name for op in ops] == ["moe_1", "moe_2", "moe_3", "moe_4"]
    dec = GraphDecoder(decoder_lm, 2, _DEC_SEQ)
    assert dec.grouped_product() == {"rows": 0, "library": 0}
    args = {key: (fn, a) for key, _, fn, a in (
        dec.prefill_fn(8), dec.decode_fn(), *dec._program_specs())[2:]}
    fn, a = args["jit_prefill.8"]
    fn.trace(*a)
    assert dec.grouped_product() == {"rows": 0, "library": 4}
    assert ops[0].grouped_product == {("chunk", 8): "library"}
    fn, a = args["jit_decode"]
    fn.trace(*a)
    assert dec.grouped_product() == {"rows": 0, "library": 8}
    assert ops[0].grouped_product == {("chunk", 8): "library",
                                      ("token", 2): "library"}
    # a forward outside serving is noted by the op and not counted here
    ops[0].grouped_product[("forward", 128)] = "library"
    assert dec.grouped_product() == {"rows": 0, "library": 8}
    ops[0].grouped_product[("token", 2)] = "rows"       # as a TPU answers
    assert dec.grouped_product() == {"rows": 1, "library": 7}
    del ops[0].grouped_product[("forward", 128)]

    eng = GenerationEngine(decoder_lm, slots=2)
    with eng:
        eng.submit(np.arange(1, 6, dtype=np.int32),
                   max_new_tokens=3).result(timeout=300)
        caches, eng._caches = eng._caches, None     # no device array left
        try:
            got = eng.stats()["moe"]["grouped_product"]
        finally:
            eng._caches = caches
    # the engine's own programs: every chunk bucket and the token step
    assert got == {"rows": 0, "library": 4 * (len(eng._decoder.buckets) + 1)}


def test_counters_ride_the_boundarys_fetch_and_its_decode_step_span(
        decoder_lm):
    """What the MoE ops count on the device reaches the host as a COPY
    beside each boundary's tokens (the counters themselves are donated to
    the next program): ``stats()`` read from another thread while streams
    are served never touches a device buffer, so it never meets a deleted
    one, and what it reads only grows; every ``decode_step`` span carries
    the totals as they stood behind its step, and a graph that counts
    nothing carries none."""
    import threading

    from flexflow_tpu.obs.trace import get_tracer
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (9, 17, 6, 12)]
    tr = get_tracer()
    tr.reset()
    tr.configure(sample_rate=1.0)
    seen, errors, done = [], [], threading.Event()
    try:
        with GenerationEngine(decoder_lm, slots=2) as eng:
            def poll():
                while not done.is_set():
                    try:
                        seen.append(sum(m["assignments"] for m in
                                        _moe_ops(eng.stats()).values()))
                    except BaseException as e:  # noqa: BLE001
                        errors.append(e)
                        return
            t = threading.Thread(target=poll)
            t.start()
            for s in [eng.submit(p, max_new_tokens=10) for p in prompts]:
                s.result(timeout=300)
            done.set()
            t.join()
            final = _moe_ops(eng.stats())
        steps = sorted((s for s in tr.snapshot()["spans"]
                        if s["name"] == "decode_step"),
                       key=lambda s: s["args"]["step"])
        tr.reset()
        with GenerationEngine(_build_lm(), slots=2) as plain:
            plain.submit(prompts[0] % 50, max_new_tokens=4).result(
                timeout=300)
            assert "moe" not in plain.stats()
        assert plain._counters_host is None
        uncounted = [s["args"] for s in tr.snapshot()["spans"]
                     if s["name"] == "decode_step"]
        assert uncounted and not any("moe_untouched" in a for a in uncounted)
    finally:
        tr.disable()
        tr.reset()
    assert not errors, errors
    assert seen and seen == sorted(seen) and seen[-1] <= sum(
        m["assignments"] for m in final.values())
    totals = [(s["args"]["moe_expert_steps"], s["args"]["moe_untouched"])
              for s in steps]
    assert len(totals) >= 10 and totals == sorted(totals)
    assert totals[-1] == (
        sum(m["token_steps"] * 8 for m in final.values()),
        round(sum(m["untouched_share"] * m["token_steps"] * 8
                  for m in final.values())))
    # 4 sparse layers of 8 experts count every step once
    assert totals[-1][0] - totals[0][0] == (len(steps) - 1) * 4 * 8
    assert 0 <= totals[-1][1] < totals[-1][0]


def test_a_released_windowed_row_is_never_read(decoder_lm):
    """Every row of every windowed entry poisoned (1e6, finite so that a
    row read AS A VALUE shows and a masked one multiplies to zero) between
    two requests: the second stream starts in rings full of what an
    earlier stream left and serves the same tokens as the forward."""
    model = decoder_lm
    rng = np.random.default_rng(5)
    first, second = (rng.integers(1, VOCAB, n).astype(np.int32)
                     for n in (27, 11))
    eng = GenerationEngine(model, slots=2)
    with eng:
        eng.submit(first, max_new_tokens=20).result(timeout=300)
        for name in eng._decoder.windowed:      # the engine is idle
            eng._caches[name] = {leaf: jnp.full_like(a, 1e6) for leaf, a in
                                 eng._caches[name].items()}
        out = [int(t) for t in
               eng.submit(second, max_new_tokens=20).result(timeout=300)]
    assert out == reference_decode(model, second, 20, _DEC_SEQ)


def test_what_a_windowed_entry_cannot_do_is_refused_by_name(decoder_lm):
    """ONE gate (``GraphDecoder.refusal``): speculation raises, migration
    raises, the prefix cache stays off and says why; a pageable graph is
    refused nothing."""
    model = decoder_lm
    dec = GraphDecoder.for_model(model, 2, _DEC_SEQ, prefill_chunk=_DEC_CHUNK)
    for what in ("prefix reuse", "speculation", "migration"):
        why = dec.refusal(what)
        assert why.startswith(what) and "attention_1" in why, why
    with pytest.raises(ValueError, match="windowed cache entry"):
        GenerationEngine(model, slots=2, draft_model=model, spec_gamma=2)
    plain = GraphDecoder.for_model(_build_lm(), 2, SEQ)
    assert plain.refusal("prefix reuse") is None and not plain.windowed
    # engines of one chunk share a decoder; another chunk ends the list of
    # chunk buckets elsewhere, so it is a decoder of its own
    assert GraphDecoder.for_model(plain.model, 2, SEQ) is plain
    chunked = GraphDecoder.for_model(plain.model, 2, SEQ, prefill_chunk=4)
    assert chunked is not plain and chunked.buckets == (2, 4)
    assert plain.buckets[-1] == SEQ
    assert GraphDecoder.for_model(plain.model, 2, SEQ, prefill_chunk=4) \
        is chunked


def test_rotary_positions_match_a_complex_rotation():
    """Both rotary forms against a rotation written by hand in complex
    numbers, at three positions: plain (the whole head, theta 10 000,
    pairs (i, i + d/2)) and partial with YaRN (the first half of the head,
    pairs (i, i + d/4), frequencies interpolated by ``factor`` below
    ``beta_slow`` rotations over the original length, kept above
    ``beta_fast``, cos and sin times ``attention_factor``; the other half
    passes).  Tolerance 1e-5: float32 cos and sin of angles up to 1e3."""
    import math

    from flexflow_tpu.ops.attention import apply_rope, rope_inv_freq
    d = 16
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 3, 2, d)).astype(np.float32)
    pos = np.array([0, 7, 1000])
    for kind, rope in _DEC_ROPE.items():
        got = np.asarray(apply_rope(jnp.asarray(x), jnp.asarray(pos), rope))
        rot = int(d * rope["partial_rotary_factor"])
        half = rot // 2
        theta = float(rope["rope_theta"])
        freq = np.array([theta ** (-2.0 * i / rot) for i in range(half)])
        att = 1.0
        if rope["rope_type"] == "yarn":
            f, orig = rope["factor"], rope["original_max_position_embeddings"]

            def dim(rotations):
                return rot * math.log(orig / (rotations * 2 * math.pi)) / (
                    2 * math.log(theta))

            lo = max(math.floor(dim(rope["beta_fast"])), 0)
            hi = min(math.ceil(dim(rope["beta_slow"])), rot - 1)
            ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0, 1)
            freq = freq / f * ramp + freq * (1 - ramp)
            att = rope["attention_factor"]
        z = (x[..., :half] + 1j * x[..., half:rot]) * att * np.exp(
            1j * pos[None, :, None, None] * freq)
        want = np.concatenate([z.real, z.imag, x[..., rot:]], axis=-1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=kind)
        inv, factor, r = rope_inv_freq(rope, d)
        assert r == rot and factor == pytest.approx(att)
        np.testing.assert_allclose(inv, freq, rtol=1e-6)


@pytest.mark.parametrize("rate", [0.0, 1.0])
def test_post_norm_graph_serves_the_tokens_it_served_before(rate):
    """``build_transformer_lm``'s tiny graph (what ``gpt1`` is built of)
    through the engine, whole prompts and chunks of 4: the tokens the
    PARENT of ISSUE 36's change served, recorded from its tree (seed 0,
    prompts from ``default_rng(36)``), bit for bit: the attention op's
    defaults trace what they traced, the scopes the walk runs them under
    (ISSUE 39) change no program, and tracing on (``trace_sample_rate``
    1) serves what tracing off serves."""
    from flexflow_tpu.obs.trace import get_tracer
    before = [[3, 41, 11, 45, 45, 54, 21, 19, 37, 6],
              [45, 44, 37, 19, 37, 6, 56, 13, 13, 60],
              [37, 56, 13, 13, 22, 16, 49, 24, 37, 17],
              [37, 6, 53, 35, 3, 37, 37, 45, 32, 13]]
    model = _build_lm()
    model.config.trace_sample_rate = rate
    rng = np.random.default_rng(36)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (3, 7, 12, 17)]
    tracer = get_tracer()
    tracer.disable()
    tracer.reset()
    try:
        for chunk in (0, 4):
            with GenerationEngine(model, slots=2,
                                  prefill_chunk=chunk) as eng:
                outs = [[int(t) for t in eng.submit(
                    p, max_new_tokens=10).result(timeout=120)]
                    for p in prompts]
            assert outs == before, chunk
        assert bool(tracer.snapshot()["spans"]) == bool(rate)
    finally:
        tracer.disable()
        tracer.reset()


@pytest.mark.parametrize("window,dtype,tol", [
    (0, "float32", 1e-5), (32, "float32", 1e-5), (32, "bfloat16", 2e-2)])
def test_paged_decode_kernel_takes_groups_and_a_window(window, dtype, tol):
    """The kernel (interpret mode) with 12 query heads over 2 key/value
    heads of size 128, without and with a window over a RING table,
    against ``_decode_attention`` on the gathered view: positions before
    the window fills, at its edge, after the ring wraps once and three
    times, and one idle slot.  Every page the kernel may not read (beyond
    a slot's position; with a window, the pages that hold nothing of the
    last 32 positions) is NaN in the pools it gets.  Tolerances as in
    ``test_paged_decode_kernel_matches_gathered_decode``."""
    from flexflow_tpu.ops.attention import _decode_attention
    from flexflow_tpu.ops.paged_decode_kernel import paged_decode_attention

    H, G, hd, page = 12, 2, 128, 16
    e = G * hd
    if window:
        pps = (window + 64) // page
        ring = pps * page
        pos = np.array([0, 5, window - 1, window, 77, window + 3, ring - 1,
                        ring, ring + 17, 3 * ring + 5], np.int32)
    else:
        pps, ring = 40, 40 * page
        pos = np.array([0, page - 1, page, 530, 77, ring - 1], np.int32)
    slots, idle = len(pos), 4
    rng = np.random.default_rng(window)
    num_pages = slots * pps
    table = (np.arange(slots)[:, None] * pps
             + np.arange(pps)[None]).astype(np.int32)
    wp = np.zeros(slots, np.int32)
    wp[idle] = num_pages
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
               for shape in ((slots, H * hd), (num_pages, page, e),
                             (num_pages, page, e)))

    def view(pool):
        return jnp.take(pool, table, axis=0).reshape(slots, ring, G, hd)

    scale = 1.0 / np.sqrt(hd)
    kpos = None
    if window:
        r = np.arange(ring)[None]
        kpos = jnp.asarray(pos[:, None] - (pos[:, None] - r) % ring)
    want = _decode_attention(q.reshape(slots, 1, H, hd), view(k), view(v),
                             jnp.asarray(pos), scale, kpos, window)
    stale = np.ones(num_pages, bool)
    for i in range(slots):
        if i != idle:
            first = max(pos[i] - window + 1, 0) // page if window else 0
            for lp in range(first, pos[i] // page + 1):
                stale[table[i, lp % pps]] = False
    poison = jnp.asarray(stale)[:, None, None]
    got = paged_decode_attention(
        q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v),
        jnp.asarray(table), jnp.asarray(pos), jnp.asarray(wp), H, scale, G,
        window)
    assert got.dtype == jnp.float32 and got.shape == (slots, H * hd)
    decoding = np.arange(slots) != idle
    np.testing.assert_allclose(
        np.asarray(got)[decoding],
        np.asarray(want).reshape(slots, H * hd)[decoding], rtol=tol,
        atol=tol)
    assert np.all(np.asarray(got)[idle] == 0.0)


@pytest.mark.parametrize("heads,kv_heads,head_dim,dtype,tol", [
    (8, 2, 128, "float32", 1e-5), (8, 2, 128, "bfloat16", 2e-2),
    (2, 2, 64, "float32", 1e-5), (2, 2, 64, "bfloat16", 2e-2)],
    ids=["groups-f32", "groups-bf16", "folded-f32", "folded-bf16"])
def test_paged_sparse_kernel_attends_over_the_chosen_set(heads, kv_heads,
                                                         head_dim, dtype,
                                                         tol):
    """The kernel with ``keep`` (interpret mode; 8 query heads over 2
    key/value heads of 128, and 2 heads of 64 on the folded row) against
    ``_decode_attention(.., keep=)`` over the gathered view, pages out of
    order, 5 120 positions a slot (two and a half copy groups of 2 048):
    slots at position 0, at a page's last row, one that keeps a tenth of its
    history, one whose FIRST 128 positions and one whole copy group
    (2 048-4 095) hold no chosen row (an empty chunk, the first one too, must
    weigh nothing: ``exp(NEG_INF - NEG_INF)`` is 1), one that keeps only
    positions past ``pos`` and its own, one that is not decoding (zero),
    and one that keeps EVERYTHING, which must be the dense paged kernel's
    output (float32: to the order of the sums over longer chunks; bfloat16:
    to the rounding of a weight).  Every page past a slot's position is NaN
    in the pools the kernel gets."""
    from flexflow_tpu.ops.attention import _decode_attention
    from flexflow_tpu.ops.paged_decode_kernel import (paged_decode_attention,
                                                      paged_sparse_attention)

    page, pps = 16, 320
    e, L = kv_heads * head_dim, 16 * 320
    pos = np.array([0, page - 1, 2700, 5119, 77, 3000, 4500], np.int32)
    slots, idle, empty, future, whole = len(pos), 4, 3, 5, 6
    rng = np.random.default_rng(heads)
    num_pages = slots * pps + 3
    table = rng.permutation(num_pages)[:slots * pps].reshape(
        slots, pps).astype(np.int32)
    wp = table[np.arange(slots), pos // page]
    wp[idle] = num_pages
    keep = rng.random((slots, L)) < 0.1
    keep[empty, :128] = keep[empty, 2048:4096] = False
    keep[future] = np.arange(L) > pos[future]
    keep[whole] = True
    keep[np.arange(slots), pos] = True
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
               for shape in ((slots, heads * head_dim), (num_pages, page, e),
                             (num_pages, page, e)))

    def view(pool):
        return jnp.take(pool, table, axis=0).reshape(slots, L, kv_heads,
                                                     head_dim)

    scale = 1.0 / np.sqrt(head_dim)
    want = _decode_attention(
        q.reshape(slots, 1, heads, head_dim), view(k), view(v),
        jnp.asarray(pos), scale, keep=jnp.asarray(keep)[:, None, None, :])
    stale = np.ones(num_pages, bool)
    for i in range(slots):
        if i != idle:
            stale[table[i, :pos[i] // page + 1]] = False
    poison = jnp.asarray(stale)[:, None, None]
    k, v = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
    args = (q, k, v, jnp.asarray(table), jnp.asarray(pos), jnp.asarray(wp))
    got = np.asarray(paged_sparse_attention(*args, jnp.asarray(keep), heads,
                                            scale, kv_heads))
    assert got.dtype == np.float32 and got.shape == (slots, heads * head_dim)
    decoding = np.arange(slots) != idle
    np.testing.assert_allclose(
        got[decoding],
        np.asarray(want, np.float32).reshape(slots, -1)[decoding], rtol=tol,
        atol=tol)
    assert np.all(got[idle] == 0.0)
    dense = np.asarray(paged_decode_attention(*args, heads, scale, kv_heads))
    np.testing.assert_allclose(got[whole], dense[whole], rtol=tol, atol=tol)


def _kernel_jaxpr_digest(fn, static, *args):
    import hashlib
    import re
    text = str(jax.make_jaxpr(fn, static_argnums=static)(*args))
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()
                          ).hexdigest()


@pytest.mark.parametrize("form,want", [
    ("one head a key/value head",
     "de33fceba70b833f0d694c38d1a8770d84e2881954687367ad084356b21b3025"),
    ("groups",
     "4dbfac730c78f59d3fbc808a9be227c576424bc7d3c5e5751b6a7ce7392f6105"),
    ("groups and a window",
     "13d0358ddebd37d42a3e01f9e8e18c7798812d1c389a8a8995e46f2b70970ae2"),
    ("a latent row",
     "9c82e5c575b1e9236298bcfcd9725d451702c2bc5f609f013ca6ab0a0ba73897")],
    ids=["folded", "groups", "window", "latent"])
def test_the_paged_kernel_without_a_chosen_set_is_the_parents(form, want,
                                                              monkeypatch):
    """``keep`` is STATIC in its presence: without it the wrappers that were
    there trace the kernel, body and all, to the jaxpr they traced to before
    the kernel learned of a chosen set (sha256 of the printed jaxpr as one
    TPU traces it, bfloat16, pages of 16, read on the parent commit under
    this suite's ``conftest.py``): ``gpt1``'s folded heads, laguna's groups
    without and with a window, pangu's latent row; ouro's is the first with
    other widths.  A PR that changes the kernel for them on purpose says so
    and moves the pins."""
    from flexflow_tpu.ops import flash_kernel, paged_decode_kernel as pk

    monkeypatch.setattr(flash_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "_interpret", lambda: False)

    def sd(shape, dtype="bfloat16"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

    def decode(slots, heads, kv, hd, pps, window):
        pool = sd((slots * pps, 16, kv * hd))
        return (pk.paged_decode_attention, (6, 7, 8, 9),
                sd((slots, heads * hd)), pool, pool, sd((slots, pps), "int32"),
                sd((slots,), "int32"), sd((slots,), "int32"), heads, 0.125,
                0 if kv == heads else kv, window)

    fn, static, *args = {
        "one head a key/value head": decode(8, 12, 12, 64, 24, 0),
        "groups": decode(8, 48, 8, 128, 64, 0),
        "groups and a window": decode(8, 64, 8, 128, 64, 512),
        "a latent row": (pk.paged_latent_attention, (5, 6),
                         sd((4, 128, 640)), sd((4 * 50, 16, 640)),
                         sd((4, 50), "int32"), sd((4,), "int32"),
                         sd((4,), "int32"), 0.0722, 512)}[form]
    assert _kernel_jaxpr_digest(fn, static, *args) == want


@pytest.mark.parametrize("why,args,ok", [
    ("6 query heads a key/value head of 128", ("tpu", "bfloat16", 48, 128,
                                               16, False, 8), True),
    ("8 a head", ("tpu", "bfloat16", 64, 128, 16, False, 8), True),
    ("a grouped head of half a tile", ("tpu", "bfloat16", 16, 64, 16, False,
                                       4), False),
    ("a group wider than the sublane tile", ("tpu", "bfloat16", 64, 128, 16,
                                            False, 2), False),
], ids=lambda x: x if isinstance(x, str) else "")
def test_paged_decode_supported_says_which_groups_it_takes(why, args, ok):
    from flexflow_tpu.ops.paged_decode_kernel import supported
    backend, dtype, *rest = args
    assert supported(backend, jnp.dtype(dtype), *rest) == ok, why


@pytest.mark.parametrize("heads,pages_per_slot,window", [
    (64, 64, 512), (48, 256, 0)], ids=["sliding", "full"])
def test_grouped_paged_decode_kernel_compiles_for_the_chip(
        v5e_device, monkeypatch, heads, pages_per_slot, window):
    """The kernel at the widths ISSUE 36's configuration serves (8 key/
    value heads of 128 under 64 and 48 query heads, bf16 pools, 128 slots,
    pages of 16; a 512 window over a ring of 64 pages, 4 096 positions
    whole), compiled by the TPU's compiler for a described v5e: what
    interpret mode cannot show (tile alignment of the lane slices, the
    buffers' fit in VMEM)."""
    from flexflow_tpu.ops import flash_kernel, paged_decode_kernel as pk
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(flash_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e_device)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, page, e = 128, 16, 8 * 128
    pages = slots * pages_per_slot
    args = (sd((slots, heads * 128), jnp.bfloat16),
            sd((pages, page, e), jnp.bfloat16),
            sd((pages, page, e), jnp.bfloat16),
            sd((slots, pages_per_slot), jnp.int32), sd((slots,), jnp.int32),
            sd((slots,), jnp.int32))
    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S, lambda: pk.paged_decode_attention
                       .lower(*args, heads, 0.088, 8, window).compile()
                       .as_text())
    assert "paged_decode_attention" in text


# ----------------------------------------------------------------------
# the serving programs' device time by graph op (ISSUE 39)
# ----------------------------------------------------------------------
def _owned(table):
    """``{owner: {part}}`` of a program's owner table."""
    out = {}
    for owner, part in table.values():
        out.setdefault(owner, set()).add(part)
    return out


@pytest.mark.parametrize("build, bucket", [(_build_lm, 16),
                                           (_build_decoder_lm, 8),
                                           (_build_latent_lm, 8)])
def test_every_graph_op_owns_instructions_of_the_serving_programs(build,
                                                                   bucket):
    """The token step and one chunk program of the tiny post-norm decoder
    and of the tiny laguna graph, compiled here: every graph op owns at
    least one instruction of each, ``sample`` owns the argmax, a
    mixture-of-experts op's instructions carry its own scopes as parts,
    and nothing but the graph's ops and ``SERVE_OWNERS`` owns anything.
    The model has NO weights installed and no pool is allocated: the
    table is a matter of shapes."""
    from flexflow_tpu.obs.device_ops import SERVE_OWNERS
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    model = build(weights=False)
    assert model._params == {}
    seq = model.input_tensors[0].shape[1]
    dec = GraphDecoder(model, 2, seq)
    dec.decode_fn()
    dec.prefill_fn(bucket)
    tables = _within(_COMPILE_LIMIT_S, dec.program_op_tables)
    assert set(tables) == {"jit_decode", f"jit_prefill_{bucket}"}
    ops = {op.name for op in model.layers}
    for name, table in tables.items():
        owned = _owned(table)
        assert ops <= set(owned), (name, ops - set(owned))
        assert set(owned) <= ops | {None, "sample"}
        assert set(owned) & set(SERVE_OWNERS) == {"sample"}
        assert any(re.search("reduce|argmax", ins) for ins, (owner, _)
                   in table.items() if owner == "sample"), name
        for op in model.layers:
            # (an op whose every instruction lies in a scope of its own
            # leaves nothing to the part ``None``)
            assert owned[op.name] | {None} == {None, *op.scopes}, (
                name, op.name)
    if build is _build_decoder_lm:
        assert _owned(tables["jit_decode"])["moe_1"] == {
            None, "moe_router", "moe_experts", "moe_shared"}
    if build is _build_latent_lm:   # the chunk's loop body keeps its parts
        for table in tables.values():
            assert _owned(table)["attention_1"] - {None} == {
                "mla_q", "mla_latent", "mla_absorb", "mla_core", "mla_out"}
    # asked again, nothing is compiled again; the copies' count shares it
    reads = dict(dec._program_reads)
    assert set(dec.pool_copies()) == {"jit_decode", f"jit_prefill.{bucket}"}
    assert dec.program_op_tables() == tables
    assert all(dec._program_reads[k] is v for k, v in reads.items())


def test_an_engine_never_started_names_its_programs_and_their_owners():
    """``GenerationEngine.program_op_tables()`` is ON DEMAND and needs no
    pool: an engine that was never started answers for the programs its
    decoder has built, each chunk bucket under a name of its own (as a
    profiler trace prints it), the token splice under ``step_io``."""
    # a model of its own: a decoder is shared by the engines of a model
    eng = GenerationEngine(_build_lm(weights=False), slots=2,
                           max_new_tokens=3)
    assert eng._caches is None
    dec = eng._decoder
    for b in dec.buckets[:2]:
        dec.prefill_fn(b)
    dec.decode_fn()
    tables = _within(_COMPILE_LIMIT_S, eng.program_op_tables)
    assert set(tables) == {"jit_decode", "jit_splice_tokens",
                           *(f"jit_prefill_{b}" for b in dec.buckets[:2])}
    assert len(dec.buckets[:2]) == 2 and eng._caches is None
    assert "step_io" in _owned(tables["jit_splice_tokens"])
    # the documented keys of pool_copies() stay
    assert set(dec.pool_copies()) == {
        "jit_decode", *(f"jit_prefill.{b}" for b in dec.buckets[:2])}


@pytest.mark.parametrize("lost", ["every owner", "attention_1", "sample"])
def test_a_table_that_leaves_an_owner_out_is_an_error_not_a_guess(
        lm, monkeypatch, lost):
    """Metadata is not in the compilation cache's key, so a cache may
    answer with an executable compiled by a tree whose programs had other
    scopes: none at all, an op under another name, one scope fewer.  Every
    owner the LOWERED text traces has to own an instruction of the
    compiled one; the error names the program and who is missing."""
    from flexflow_tpu.obs import device_ops
    from flexflow_tpu.serving.generation import decoder as decoder_mod

    dec = decoder_mod.GraphDecoder(lm, 2, SEQ)
    dec.decode_fn()

    def stale(text, owners, parts=()):
        return {ins: (None, None) if lost in ("every owner", owner)
                else (owner, part) for ins, (owner, part) in
                device_ops.table_from_hlo(text, owners, parts).items()}

    monkeypatch.setattr(decoder_mod, "table_from_hlo", stale)
    with pytest.raises(RuntimeError, match="clear the cache") as e:
        dec.program_op_tables()
    assert "jit_decode gives no instruction to" in str(e.value)
    if lost != "every owner":
        assert f"to {lost}," in str(e.value)
    # the copies' count is no matter of scopes: it answers all the same
    assert set(dec.pool_copies()) == {"jit_decode"}


def test_a_stale_token_splice_is_an_error_too(monkeypatch):
    from flexflow_tpu.serving.generation import engine as engine_mod

    eng = GenerationEngine(_build_lm(weights=False), slots=2,
                           max_new_tokens=3)
    monkeypatch.setattr(engine_mod, "table_from_hlo",
                        lambda text, owners: {"fusion.1": (None, None)})
    with pytest.raises(RuntimeError, match="jit_splice_tokens gives no "
                       "instruction to step_io.*clear the cache"):
        eng.program_op_tables()


@pytest.mark.parametrize("build, bucket", [(_build_lm, 16),
                                           (_build_decoder_lm, 8),
                                           (_build_latent_lm, 8)])
def test_the_scopes_change_no_lowered_program(build, bucket, monkeypatch):
    """The token step and one chunk program lowered with the scopes and
    with ``jax.named_scope`` made a no-op: the same text, so the same
    sha256 (a scope is metadata, which the text carries only when asked
    for locations).  Lowered, never compiled: nothing without scopes
    reaches the compilation cache."""
    import hashlib
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    def digests():
        model = build(weights=False)
        dec = GraphDecoder(model, 2, model.input_tensors[0].shape[1])
        dec.decode_fn()
        dec.prefill_fn(bucket)
        return {name: hashlib.sha256(
                    fn.lower(*args).as_text().encode()).hexdigest()
                for _, name, fn, args in dec._program_specs()}

    scoped = digests()
    assert set(scoped) == {"jit_decode", f"jit_prefill_{bucket}"}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert digests() == scoped


# ----------------------------------------------------------------------
# latent attention: one shared row a token in one page-major leaf
# (ISSUE 41)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def latent_lm():
    return _build_latent_lm()


def _latent_op(seed=41, dtype="float32"):
    """A ``LatentAttention`` op of the small size with random weights."""
    from flexflow_tpu.ops.latent_attention import LatentAttention
    x = Tensor(shape=(1, 48, 64), dtype="float32", name="x")
    op = LatentAttention("attention_0", x, 4, eps=1e-5, **_LAT)
    rng = np.random.default_rng(seed)
    params = {w.name: jnp.asarray(
        1.0 + 0.1 * rng.standard_normal(w.shape) if w.name.endswith("norm")
        else rng.standard_normal(w.shape) / np.sqrt(w.shape[-1]), dtype)
        for w in op.weights}
    return op, params


def test_the_absorbed_steps_equal_the_expanded_forward_row_for_row():
    """One op, float32: ``forward`` (EXPANDED, dense core) over 48
    positions against the same positions SERVED through a paged latent
    cache: prefill in chunks of 8 (the last one five real rows of eight:
    the chunk's blocked, expanded core), then a verify window of three and
    token steps (both ABSORBED, over the gathered rows) for slot 0 of two,
    slot 1 idle, its writes dropped.  Every row within 2e-5 of the
    forward's: the two forms are the same mathematics reassociated, and
    float32 sums in another order.  The cache is ONE leaf of 128-lane rows
    whose padding stays zero."""
    op, params = _latent_op()
    ctx = OpContext(training=False, compute_dtype="float32", mesh=None)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 48, 64)),
                    jnp.float32)
    want = np.asarray(op.forward(params, [x], ctx)[0])[0]
    ent = op.serve_state(2, 8, 16, None)
    assert list(ent["shapes"]) == ["kv"] and ent["kind"] == "kv"
    assert ent["shapes"]["kv"] == (8, 16, 128) and op.row_values == 40
    state = {"kv": jnp.zeros(ent["shapes"]["kv"], jnp.float32)}
    table = jnp.asarray([[5, 2, 7, 8], [8, 8, 8, 8]], jnp.int32)
    got = np.zeros_like(want)
    for start, length in ((0, 8), (8, 8), (16, 5)):
        rows = jnp.pad(x[:, start:start + length],
                       ((0, 0), (0, 8 - length), (0, 0)))
        out, state = op.serve_step(params, [rows], state, ServeStep(
            "chunk", table[0], start=jnp.int32(start),
            length=jnp.int32(length), slot=jnp.int32(0), no_page=8), ctx)
        got[start:start + length] = np.asarray(out[0])[0, :length]

    def step(kind, start, width):
        nonlocal state
        pos = np.arange(start, start + width)
        wp = np.stack([np.asarray(table)[0, pos // 16], np.full(width, 8)])
        wr = np.stack([pos % 16, np.zeros(width, np.int64)])
        if kind == "token":
            wp, wr = wp[:, 0], wr[:, 0]
        rows = jnp.concatenate([x[:, start:start + width],
                                jnp.zeros((1, width, 64))], axis=0)
        out, state = op.serve_step(params, [rows], state, ServeStep(
            kind, table, pos=jnp.asarray([start, 0], jnp.int32),
            write_pages=jnp.asarray(wp, jnp.int32),
            write_rows=jnp.asarray(wr, jnp.int32), no_page=8), ctx)
        got[start:start + width] = np.asarray(out[0])[0]

    step("window", 21, 3)
    for t in range(24, 48):
        step("token", t, 1)
    assert op.decode_core == "gathered"
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    pool = np.asarray(state["kv"])
    assert np.all(pool[..., 40:] == 0) and np.abs(pool[5, :, :40]).min() > 0
    assert np.all(pool[[0, 1, 3, 4, 6]] == 0)      # pages of nobody


def test_latent_graph_serves_what_its_forward_computes(latent_lm):
    """Prefill in chunks of 8 then decoding through the engine's latent
    pages against the graph's own full forward at every served position
    (float32: the same tokens), prompts that pass several pages and
    chunks, two streams at once.  The graph's state is one leaf a layer in
    the SHARED pool, so nothing is refused; ``stats()`` says what a token
    costs by entry, counts the latent layers under a kind of their own and
    the routing of the experts HELD."""
    model = latent_lm
    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, VOCAB, n).astype(np.int32)
               for n in (5, 8, 13, 23, 37)]
    eng = GenerationEngine(model, slots=2)
    dec = eng._decoder
    assert dec.pageable and not dec.windowed
    for what in ("prefix reuse", "speculation", "migration"):
        assert dec.refusal(what) is None
    for name in ("attention_0", "attention_1"):
        assert dec.layout[name]["shapes"] == {"kv": (dec.num_pages, 16, 128)}
    plan = eng.kv_plan
    assert plan["page_bytes"] == 2 * 16 * 128 * 4 and not plan["window_bytes"]
    with eng:
        outs = [[int(t) for t in s.result(timeout=300)] for s in
                [eng.submit(p, max_new_tokens=16) for p in prompts]]
        snap = eng.stats()
    for p, out in zip(prompts, outs):
        assert out == reference_decode(model, p, 16, _LAT_SEQ)
    assert snap["decode_attention"] == {
        "paged": 0, "gathered": 2, "latent": {"paged": 0, "gathered": 2}}
    assert snap["kv_pages"]["full"]["bytes_per_token"] == {
        "attention_0": 512, "attention_1": 512}
    moe = snap["moe"]["moe_1"]
    assert moe["held"] == 8 and len(moe["load"]) == 8
    # 4 choices a token over 16 experts of which 8 are here: about half
    tokens = sum(len(p) for p in prompts) + 5 * 15
    assert 0.25 * 4 * tokens < moe["assignments"] < 0.75 * 4 * tokens


def test_latent_pages_are_lent_rolled_back_and_shipped(latent_lm):
    """The three things a windowed entry refuses, on the latent graph:
    a prompt that REUSES another's first two pages, a divergent draft whose
    windows are partly REJECTED, and a stream that prefills on one engine
    and decodes on another each serve the tokens the graph's own forward
    gives (float32)."""
    from flexflow_tpu.fflogger import silenced
    from tests.serving_fixtures import build_disagg

    model = latent_lm
    rng = np.random.default_rng(42)
    first = rng.integers(1, VOCAB, 40).astype(np.int32)
    second = np.concatenate([first[:33], rng.integers(1, VOCAB, 6)]).astype(
        np.int32)
    refs = [reference_decode(model, p, 10, _LAT_SEQ) for p in (first, second)]
    with silenced("serve"):
        with GenerationEngine(model, slots=2) as eng:
            outs = [[int(t) for t in eng.submit(
                p, max_new_tokens=10).result(timeout=300)]
                for p in (first, second)]
            snap = eng.stats()
        assert outs == refs and snap["prefix_hit_tokens"] == 32
        draft = _build_latent_lm(seed=7)
        with GenerationEngine(model, slots=2, draft_model=draft,
                              spec_gamma=3) as eng:
            outs = [[int(t) for t in eng.submit(
                p, max_new_tokens=10).result(timeout=300)]
                for p in (first, second)]
            snap = eng.stats()
        assert outs == refs and snap["spec"] == "on"
        assert 0 < snap["spec_proposed_tokens"] > snap["spec_accepted_tokens"]
        router, fleets, (pf_eng, dc_eng) = build_disagg(
            model, 2, _LAT_SEQ, _LAT_CHUNK, prefix_cache="off", pf_pace_s=0.0)
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


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
def test_paged_latent_kernel_matches_the_gathered_rows(dtype, tol):
    """The kernel's latent form (interpret mode): 20 query heads over ONE
    row of 256 lanes whose first 128 are the values, against the absorbed
    scores and values over the gathered rows, at positions inside the first
    page, at a page's edge, past one copy group and one idle slot.  Every
    page the kernel may not read is NaN in the pool it gets.  Tolerances as
    in ``test_paged_decode_kernel_matches_gathered_decode``."""
    from flexflow_tpu.ops.paged_decode_kernel import paged_latent_attention

    H, e, lanes, page, pps = 20, 256, 128, 16, 40
    pos = np.array([0, page - 1, page, 530, 77, pps * page - 1], np.int32)
    slots, idle = len(pos), 4
    rng = np.random.default_rng(5)
    num_pages = slots * pps
    table = (np.arange(slots)[:, None] * pps
             + np.arange(pps)[None]).astype(np.int32)
    wp = np.zeros(slots, np.int32)
    wp[idle] = num_pages
    q = jnp.asarray(rng.standard_normal((slots, H, e)), dtype)
    pool = jnp.asarray(rng.standard_normal((num_pages, page, e)), dtype)
    scale = 1.0 / np.sqrt(e)
    view = jnp.take(pool, table, axis=0).reshape(slots, pps * page, e)
    s = jnp.einsum("nhe,nle->nhl", q, view,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(np.arange(pps * page)[None, None] > pos[:, None, None],
                  -1e30, s)
    want = jnp.einsum("nhl,nlc->nhc", jax.nn.softmax(s, axis=-1).astype(
        view.dtype), view[..., :lanes], preferred_element_type=jnp.float32)
    stale = np.ones(num_pages, bool)
    for i in range(slots):
        if i != idle:
            stale[table[i, :pos[i] // page + 1]] = False
    got = paged_latent_attention(
        q, jnp.where(jnp.asarray(stale)[:, None, None], jnp.nan, pool),
        jnp.asarray(table), jnp.asarray(pos), jnp.asarray(wp), scale, lanes)
    assert got.dtype == jnp.float32 and got.shape == (slots, H, lanes)
    decoding = np.arange(slots) != idle
    np.testing.assert_allclose(np.asarray(got)[decoding],
                               np.asarray(want)[decoding], rtol=tol, atol=tol)
    assert np.all(np.asarray(got)[idle] == 0.0)


@pytest.mark.parametrize("why,args,ok", [
    ("a 576-value row stored as five lane tiles",
     ("tpu", "bfloat16", 128, 640, 16, False, 1, 512), True),
    ("the bare 576-wide row: four and a half tiles",
     ("tpu", "bfloat16", 128, 576, 16, False, 1, 512), False),
    ("values that end mid-tile",
     ("tpu", "bfloat16", 128, 640, 16, False, 1, 448), False),
    ("more heads than the rows of one product",
     ("tpu", "bfloat16", 512, 640, 16, False, 1, 512), False),
    ("across chips", ("tpu", "bfloat16", 128, 640, 16, True, 1, 512), False),
    ("no TPU", ("cpu", "bfloat16", 128, 640, 16, False, 1, 512), False),
], ids=lambda x: x if isinstance(x, str) else "")
def test_paged_decode_supported_says_which_latent_rows_it_takes(why, args,
                                                                ok):
    from flexflow_tpu.ops.paged_decode_kernel import supported
    backend, dtype, *rest = args
    assert supported(backend, jnp.dtype(dtype), *rest[:-1],
                     value_lanes=rest[-1]) == ok, why


def test_paged_latent_kernel_compiles_for_the_chip(v5e_device, monkeypatch):
    """The kernel at the widths ISSUE 41's configuration serves (128 query
    heads over one row of 640 lanes, values its first 512, bf16, 32 slots
    of 800 pages of 16), compiled by the TPU's compiler for a described
    v5e: what interpret mode cannot show."""
    from flexflow_tpu.ops import flash_kernel, paged_decode_kernel as pk
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(flash_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e_device)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, pps = 32, 800
    args = (sd((slots, 128, 640), jnp.bfloat16),
            sd((slots * pps, 16, 640), jnp.bfloat16),
            sd((slots, pps), jnp.int32), sd((slots,), jnp.int32),
            sd((slots,), jnp.int32))
    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S, lambda: pk.paged_latent_attention
                       .lower(*args, 0.0722, 512).compile().as_text())
    assert "paged_latent_attention" in text


@pytest.mark.parametrize("bucket", [512, 2])
def test_latent_chunk_kernel_compiles_for_the_chip(v5e_device, monkeypatch,
                                                   bucket):
    """The chunk's kernel (``ops/latent_chunk_kernel.py``) at the widths
    that configuration serves (128 heads of nope 128 / rope 64 / v 128 over
    rows of 640 lanes, 800 pages of 16 a slot, bf16), the widest and the
    narrowest bucket, compiled by the TPU's compiler for a described v5e:
    the tiling, the copies and the VMEM budget, which interpret mode cannot
    show (``tests/test_latent_chunk_kernel.py`` has the arithmetic)."""
    from flexflow_tpu.ops import latent_chunk_kernel as lk
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(lk, "_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e_device)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    args = (sd((bucket, 128, 128), jnp.bfloat16),
            sd((bucket, 128, 64), jnp.bfloat16),
            sd((32 * 800 + 1, 16, 640), jnp.bfloat16), sd((800,), jnp.int32),
            sd((128, 256, 512), jnp.bfloat16), sd((), jnp.int32),
            sd((), jnp.int32))
    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S, lambda: lk.latent_chunk_attention
                       .lower(*args, scale=0.0722, rank=512, keys=512)
                       .compile().as_text())
    assert "latent_chunk_attention" in text and "while" not in text


def test_paged_index_kernel_compiles_for_the_chip(v5e_device, monkeypatch):
    """The token step's choosing kernel (``ops/paged_index_kernel.py``) at
    the widths ISSUE 44's configuration serves (16 index heads of 64 over
    stored rows of 128 lanes, 24 slots of 1 568 pages of 16, bf16, 2 048 of
    25 088 positions chosen), compiled by the TPU's compiler for a described
    v5e: the one-row stores into the score block, the scalar searches and
    the VMEM budget, which interpret mode cannot show
    (``tests/test_paged_index_kernel.py`` has the arithmetic)."""
    from flexflow_tpu.ops import paged_index_kernel as pk
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e_device)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, pps = 24, 1568
    args = (sd((slots, 16, 64), jnp.bfloat16), sd((slots, 16), jnp.float32),
            sd((slots * pps, 16, 128), jnp.bfloat16),
            sd((slots, pps), jnp.int32), sd((slots,), jnp.int32),
            sd((slots,), jnp.int32))
    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S, lambda: pk.paged_index_select
                       .lower(*args, 2048).compile().as_text())
    assert "paged_index_select" in text


def _sparse_token_step_for_the_chip(v5e_device, monkeypatch, slots, seq,
                                    d_model, heads, kv_heads, sparse):
    """``(decoder, compiled text)`` of the WHOLE token step of a one-layer
    graph with a learned selection (bf16, heads of 128, pages of 16), as one
    TPU traces it, compiled by the TPU's compiler for a described v5e."""
    from flexflow_tpu.models import build_decoder_lm
    from flexflow_tpu.ops import (attention as attn_mod, flash_kernel,
                                  paged_decode_kernel, paged_index_kernel)

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    for mod in (flash_kernel, paged_decode_kernel, paged_index_kernel):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    cfg = ff.FFConfig(batch_size=2, compute_dtype="bfloat16", seed=0)
    cfg.serve_gen_slots, cfg.serve_gen_max_seq = slots, seq
    cfg.serve_prefill_chunk, cfg.serve_kv_page = 64, 16
    model = build_decoder_lm(
        cfg, [{"attention": "full_attention", "heads": heads,
               "mlp": "dense"}],
        d_model=d_model, head_dim=128, num_kv_heads=kv_heads, d_ff=256,
        vocab_size=512, seq_len=seq, qk_norm=1e-6, sparse=sparse,
        rope={"full_attention": {"rope_theta": 1e4}})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=ff.MachineMesh({"n": 1}))
    dec = GraphDecoder(model, slots, seq, prefill_chunk=64)
    fn = dec.decode_fn()
    (args,) = [a for _, _, f, a in dec._program_specs(v5e_device) if f is fn]
    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S,
                       lambda: fn.lower(*args).compile().as_text())
    return dec, text


def test_the_sparse_token_step_chooses_without_a_sort_or_a_view(
        v5e_device, monkeypatch):
    """A table of MORE pages than the op chooses rows (128 of 4 096
    positions, 256 pages a slot): the ``"rows"`` form.  The program holds
    the choosing kernel, no ``sort`` anywhere (``jax.lax.top_k`` is one to
    this compiler) and, of its gathers, none under ``dsa_index`` (no view of
    ``ik`` is written out) and ONE under ``dsa_select``, each chosen row's
    page from the table (4 x 128 int32): the list comes by rank and one-hot
    products, and the only rows gathered are the core's K and V."""
    dec, text = _sparse_token_step_for_the_chip(
        v5e_device, monkeypatch, 4, 4096, 256, 4, 2,
        {"index_heads": 4, "index_dim": 64, "topk": 128})
    assert dec.decode_attention()["sparse"] == {"rows": 1, "paged": 0,
                                                "gathered": 0}
    assert "paged_index_select" in text
    assert "paged_sparse_attention" not in text
    lines = text.splitlines()
    assert not [l for l in lines if " sort(" in l]
    gathers = [l for l in lines if " gather(" in l]
    assert not [l for l in gathers if "dsa_index" in l]
    (pages,) = [l for l in gathers if "dsa_select" in l]
    assert " s32[4,128]" in pages
    assert len([l for l in gathers if "dsa_core" in l]) == 2, gathers


def test_the_sparse_token_step_reads_its_pages_under_the_chosen_set(
        v5e_device, monkeypatch):
    """A table of no more pages than the op chooses rows, at the widths
    ISSUE 44's configuration serves (24 slots of 1 568 pages, 2 048 of
    25 088 positions chosen by 16 index heads of 64, 32 query heads over 4
    key/value heads of 128): the ``"paged"`` form.  The program holds both
    kernels (the choice, and the paged decode kernel under the set as a
    mask), no ``sort``, and NO gather under any of the op's three scopes:
    the set is never a list, and no row and no page id is looked up."""
    dec, text = _sparse_token_step_for_the_chip(
        v5e_device, monkeypatch, 24, 25088, 2048, 32, 4,
        {"index_heads": 16, "index_dim": 64, "topk": 2048})
    assert dec.decode_attention() == {
        "paged": 1, "gathered": 0,
        "sparse": {"rows": 0, "paged": 1, "gathered": 0}}
    assert "paged_index_select" in text and "paged_sparse_attention" in text
    assert "paged_decode_attention" not in text
    lines = text.splitlines()
    assert not [l for l in lines if " sort(" in l]
    assert not [l for l in lines if " gather(" in l and "dsa_" in l]
    for part in ("dsa_index", "dsa_select", "dsa_core"):
        assert [l for l in lines if part in l], part


def test_paged_sparse_kernel_compiles_for_the_chip(v5e_device, monkeypatch):
    """The paged decode kernel with a chosen set at those widths (32 query
    heads over 4 key/value heads of 128, bf16 pools, 24 slots of 1 568 pages
    of 16, the set a ``(196, 128)`` int32 block a slot), compiled by the
    TPU's compiler for a described v5e: the block's one-row loads side by
    side, the lane slices and the VMEM budget, which interpret mode cannot
    show."""
    from flexflow_tpu.ops import flash_kernel, paged_decode_kernel as pk
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(flash_kernel, "_interpret", lambda: False)
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    chip = SingleDeviceSharding(v5e_device)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    slots, pps = 24, 1568
    pool = sd((slots * pps, 16, 4 * 128), jnp.bfloat16)
    args = (sd((slots, 32 * 128), jnp.bfloat16), pool, pool,
            sd((slots, pps), jnp.int32), sd((slots,), jnp.int32),
            sd((slots,), jnp.int32), sd((slots, pps * 16), jnp.bool_))
    with _no_compilation_cache():
        text = _within(_COMPILE_LIMIT_S, lambda: pk.paged_sparse_attention
                       .lower(*args, 32, 0.088, 4).compile().as_text())
    assert "paged_sparse_attention" in text


def test_a_looped_token_step_reads_every_passs_region_in_place(
        v5e_device, monkeypatch):
    """The WHOLE token step of a stack run three times (two layers at the
    widths of the cell that serves one: 16 heads of 128 with as many
    key/value heads, pages of 16, bfloat16), as one TPU traces it, compiled
    by the TPU's compiler for a described v5e: the passes are ONE ``while``
    whose body holds the paged decode kernel (each layer's call reads its
    pass's region of the leaves where it lies), and no pool-sized array is
    copied, though every leaf is carried round the loop."""
    from flexflow_tpu.models import build_decoder_lm
    from flexflow_tpu.ops import (attention as attn_mod, flash_kernel,
                                  paged_decode_kernel)

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    for mod in (flash_kernel, paged_decode_kernel):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    cfg = ff.FFConfig(batch_size=2, compute_dtype="bfloat16", seed=0)
    cfg.param_dtype = "bfloat16"
    cfg.serve_gen_slots, cfg.serve_gen_max_seq = 4, 448
    cfg.serve_prefill_chunk, cfg.serve_kv_page = 64, 16
    model = build_decoder_lm(
        cfg, [{"attention": "full_attention", "heads": 16,
               "mlp": "dense"}] * 2,
        d_model=2048, head_dim=128, num_kv_heads=16, d_ff=512,
        vocab_size=512, seq_len=448, sandwich=True, loops=3, exit_gate=1.0,
        rope={"full_attention": {"rope_theta": 1e6}})[0]
    model.compile(ff.SGDOptimizer(lr=0.01), mesh=ff.MachineMesh({"n": 1}))
    dec = GraphDecoder(model, 4, 448, prefill_chunk=64)
    assert dec.layout["attention_1"]["shapes"]["v"] == (3 * 4 * 28, 16, 2048)
    fn = dec.decode_fn()
    with _no_compilation_cache():
        copies = _within(_COMPILE_LIMIT_S,
                         lambda: dec.pool_copies(v5e_device))
        (args,) = [a for _, _, f, a in dec._program_specs(v5e_device)
                   if f is fn]
        text = fn.lower(*args).compile().as_text()
    assert copies == {"jit_decode": {"count": 0, "bytes": 0}}
    assert dec.decode_attention() == {"paged": 2, "gathered": 0}
    assert "paged_decode_attention" in text
    assert len([l for l in text.splitlines() if " while(" in l]) == 1


def test_the_serving_programs_of_the_graphs_that_were_there_are_the_parents():
    """The token step and one chunk program of the tiny post-norm decoder
    (``gpt1``'s family) and of the tiny laguna graph lower to the text they
    lowered to before latent attention, a sigmoid router and held experts
    arrived (sha256 of the lowered text, read on the parent commit under
    this suite's ``conftest.py``, whose matmul precision the text carries): what
    this PR added is beside their paths, not in them.  A PR that changes
    one of these programs on purpose says which and why, and moves the pin."""
    import hashlib

    want = {
        (_build_lm, "jit_prefill_16"):
        "b09e3d9aefb39bba60b5c0c82dd722b992124c433f32427ef418ae293e338166",
        (_build_lm, "jit_decode"):
        "880cc7faadb841ee3bfb4b793a375b4811c3673ff5e5208d71706c99fa4174b7",
        (_build_decoder_lm, "jit_prefill_8"):
        "e7323eba5c2197689466a7f701e6cce5562086a5afd78e677a332b0c02227a43",
        (_build_decoder_lm, "jit_decode"):
        "115301c4e49c0081d844432de4388659bd14b1c596d1e7c0976ef60b20068389"}
    got = {}
    for build, bucket in ((_build_lm, 16), (_build_decoder_lm, 8)):
        model = build(weights=False)
        dec = GraphDecoder(model, 2, model.input_tensors[0].shape[1])
        dec.decode_fn()
        dec.prefill_fn(bucket)
        for _, name, fn, args in dec._program_specs():
            got[build, name] = hashlib.sha256(
                fn.lower(*args).as_text().encode()).hexdigest()
    assert got == want
