"""The repo's own grouped matmul (``ops/grouped_matmul_kernel.py``) on the
CPU, in Pallas' interpret mode: parity with ``jax.lax.ragged_dot`` on the
serve cell's two shapes scaled down and on the edges of the walk, the
``supported()`` table, ``MoE._experts`` through either core, and the TPU
lowering of a served token program (lowered only: nothing here loads the
TPU's library)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.ops import grouped_matmul_kernel as gk

TINY = os.path.join(os.path.dirname(__file__), "perfbench", "data", "tiny",
                    "tiny-xs2.serve.json")


def _even(rows, groups, seed=0):
    return np.random.default_rng(seed).multinomial(
        rows, np.ones(groups) / groups)


# (rows, groups, k, n, sizes): the cell's token step (1 024 rows over 256
# groups, 4 a group) and prompt chunk (4 096 rows, 16 a group) at an
# eighth of the groups and a sixteenth of the widths, up product and down
# product, row tiles of 128; then the edges of the walk at tiles of 16-64
CASES = {
    "step_up": (128, 32, 128, 64, _even(128, 32, 1)),
    "step_down": (128, 32, 32, 128, _even(128, 32, 2)),
    "chunk_up": (512, 32, 128, 64, _even(512, 32, 3)),
    "chunk_down": (512, 32, 32, 128, _even(512, 32, 4)),
    "two_lane_tiles": (64, 8, 128, 256, _even(64, 8, 5)),
    "empty_groups": (64, 8, 32, 128, [0, 20, 0, 0, 30, 14, 0, 0]),
    "no_rows_at_all": (32, 4, 32, 128, [0, 0, 0, 0]),
    "one_group_holds_every_row": (64, 8, 32, 128, [0, 0, 0, 64, 0, 0, 0, 0]),
    "boundaries_inside_a_tile": (32, 8, 32, 128, [3, 1, 5, 2, 7, 4, 6, 4]),
    "a_group_spans_several_tiles": (96, 4, 32, 128, [5, 70, 1, 20]),
    "one_row_tile_of_sixteen": (16, 8, 32, 128, [2, 0, 5, 1, 0, 3, 4, 1]),
    "rows_past_the_sum": (64, 8, 32, 128, [4, 0, 9, 1, 0, 13, 2, 6]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_parity_with_ragged_dot(case, dtype):
    """Every row of a group equals the library's, to the order of the sums
    (the same operands, f32 accumulation on both sides); rows past
    ``sum(group_sizes)`` are not compared: undefined here."""
    rows, groups, k, n, sizes = CASES[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((groups, k, n)) * 0.2, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    got = gk.ragged_dot_rows(lhs, rhs, sizes)
    assert got.shape == want.shape and got.dtype == jnp.float32
    live = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_visits_every_pair_once_and_no_empty_group(case):
    """``visits`` against the walk written out in Python: every (row tile,
    group) pair that shares a row, in row order, nothing else; the static
    tail repeats the last real visit."""
    rows, groups, _, _, sizes = CASES[case]
    tm = gk.row_tile(rows)
    offsets, group, tile, total = (np.asarray(a) for a in gk.visits(
        jnp.asarray(sizes, jnp.int32), rows))
    want, start = [], 0
    for g, size in enumerate(sizes):
        want += [(t, g) for t in range(start // tm, -(-(start + size) // tm))
                 if size]
        start += size
    assert total.tolist() == [len(want)]
    assert len(group) == len(tile) == rows // tm + groups - 1
    assert list(zip(tile, group))[:len(want)] == want
    assert offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    if want:    # past the end: the last visit again, so no copy moves
        assert set(zip(tile[len(want):], group[len(want):])) <= {want[-1]}


def test_held_experts_rolled_to_the_front():
    """What ``MoE._experts`` hands over on an ``e`` shard: the counts of
    the experts HELD, their rows rolled to the front, the others' rows
    behind them (undefined in the result)."""
    rng = np.random.default_rng(7)
    sizes = np.array([6, 0, 11, 3, 9, 0, 2, 17])        # 48 rows, 8 experts
    first, held = 2, 4
    lhs = rng.standard_normal((48, 32)).astype(np.float32)
    rhs = rng.standard_normal((8, 32, 128)).astype(np.float32)
    start = int(sizes[:first].sum())
    rolled = jnp.asarray(np.roll(lhs, -start, axis=0))
    counts = jnp.asarray(sizes[first:first + held], jnp.int32)
    got = gk.ragged_dot_rows(rolled, jnp.asarray(rhs[first:first + held]),
                             counts)
    live = int(counts.sum())
    want = jax.lax.ragged_dot(jnp.asarray(lhs), jnp.asarray(rhs),
                              jnp.asarray(sizes, jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[start:start + live],
                               rtol=1e-5, atol=1e-5)


# the cell's token step on a v5e, and what each change to it does
_YES = dict(backend="tpu", dtype=jnp.bfloat16, rows=1024, groups=256,
            k=2048, n=1024, distributed=False, training=False)


@pytest.mark.parametrize("change,want", [
    ({}, True),
    ({"rows": 4096}, True),                     # the prompt chunk
    ({"k": 512, "n": 2048}, True),              # the down product
    ({"dtype": jnp.float32}, True),
    ({"rows": 16}, True),                       # the smallest chunk bucket
    ({"backend": "cpu"}, False),
    ({"backend": "gpu"}, False),
    ({"dtype": jnp.float16}, False),
    ({"k": 2040}, False),                       # an odd K
    ({"n": 1000}, False),
    ({"k": 1 << 15}, False),                    # no (K, 128) block fits
    ({"rows": 1032}, False),                    # not whole row tiles
    ({"rows": 512 * 256}, False),               # 512 rows a group: library
    ({"rows": 8192, "groups": 4}, False),       # training's thousands a group
    ({"distributed": True}, False),             # an e axis of several shards
    ({"training": True}, False),
], ids=lambda v: "-".join(f"{k}={getattr(x, '__name__', x)}"
                          for k, x in v.items()) if isinstance(v, dict)
   else str(v))
def test_supported(change, want):
    assert gk.supported(**{**_YES, **change}) is want


def test_row_and_lane_tiles():
    # every chunk bucket of the cell (2 .. 512 tokens x 8) and its step
    assert [gk.row_tile(r) for r in (16, 32, 64, 128, 1024, 4096)] == [
        16, 32, 64, 128, 128, 128]
    assert gk.row_tile(48) == 16 and gk.row_tile(96) == 32
    assert gk.row_tile(8) == 0 and gk.row_tile(1032) == 0
    # K whole, 4 MB a block: the cell's two products whole in bfloat16
    assert gk._lane_tile(2048, 1024, 2) == 1024
    assert gk._lane_tile(512, 2048, 2) == 2048
    assert gk._lane_tile(2048, 1024, 4) == 512
    assert gk._lane_tile(2048, 768, 4) == 384       # a divisor of n
    assert gk._lane_tile(1 << 16, 1024, 2) == 0
    assert gk._lane_tile(32, 48, 4) == 48           # the interpreter's


def _tiny_moe():
    """One sparse layer at the widths of the laguna tiny preset (read, not
    edited), with weights of its own."""
    from flexflow_tpu.ops.moe import MoE
    from flexflow_tpu.tensor import Tensor

    with open(TINY) as f:
        cfg = json.load(f)["config"]
    d = cfg["hidden_size"]
    x = Tensor(shape=(1, 8, d), dtype="float32", name="x")
    op = MoE("moe", x, cfg["num_experts"], cfg["moe_intermediate_size"],
             k=cfg["num_experts_per_tok"], capacity_factor=None,
             aux_loss_weight=0.0, gated=True,
             shared_d_ff=cfg["shared_expert_intermediate_size"],
             routed_scale=cfg["moe_routed_scaling_factor"])
    rng = np.random.default_rng(36)
    params = {w.name: jnp.asarray(rng.standard_normal(w.shape) * 0.3,
                                  jnp.float32) for w in op.weights}
    return op, params, d


@pytest.mark.parametrize("tokens", [8, 64])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_experts_through_the_kernel_equal_through_the_library(
        monkeypatch, dtype, tokens):
    """The whole op, one prompt chunk (8 tokens x 2 choices: one row tile)
    and a longer one, with the core STEERED (the test's business: on the
    CPU the op itself chooses the library): the same output either way,
    to the order of the sums in float32 and to bfloat16's rounding of
    the hidden rows in bfloat16."""
    from flexflow_tpu.op import OpContext
    from flexflow_tpu.ops.moe import MoE

    op, params, d = _tiny_moe()
    x = jnp.asarray(np.random.default_rng(tokens).standard_normal(
        (1, tokens, d)), jnp.float32)
    ctx = OpContext(training=False, compute_dtype=dtype, mesh=None)
    out = {}
    for core in ("library", "rows"):
        monkeypatch.setattr(MoE, "_grouped_core", lambda *a, c=core: c)
        out[core] = np.asarray(op.forward(params, [x], ctx)[0], np.float32)
        assert op.grouped_product == {("forward", tokens): core}
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out["rows"], out["library"], rtol=tol,
                               atol=tol)


def test_the_op_chooses_the_library_on_the_cpu_and_in_training():
    from flexflow_tpu.op import OpContext

    op, params, d = _tiny_moe()
    x = jnp.ones((1, 8, d), jnp.float32)
    for training in (False, True):
        op.forward(params, [x], OpContext(training=training,
                                          compute_dtype="float32", mesh=None))
        assert op.grouped_product == {("forward", 8): "library"}


# ---- the lowering for a TPU (lowered, not compiled) ----------------------
_LAYERS = [{"attention": "full_attention", "heads": 4, "mlp": "dense"},
           {"attention": "sliding_attention", "heads": 4, "mlp": "sparse"},
           {"attention": "full_attention", "heads": 4, "mlp": "sparse"}]
_ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 10000,
                            "partial_rotary_factor": 1},
         "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                               "partial_rotary_factor": 1}}


def _token_program_text(model, slots, seq):
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    dec = GraphDecoder(model, slots, seq)
    dec.decode_fn()
    (fn, args), = [(fn, args) for key, _, fn, args in dec._program_specs()
                   if key == "jit_decode"]
    return dec, fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def test_the_served_token_program_holds_the_kernel_for_every_sparse_layer(
        monkeypatch):
    """A decoder with two sparse layers of lane-aligned widths (8 slots x 2
    choices: one 16-row tile), in bfloat16, its token program lowered for
    a TPU with the backend's answer steered: each sparse layer calls the
    kernel twice (up, down) and ``jax.lax.ragged_dot`` is gone; unsteered,
    the same program holds the library's and no kernel."""
    from flexflow_tpu.models import build_decoder_lm

    def built():
        cfg = ff.FFConfig(batch_size=2, compute_dtype="bfloat16", seed=0)
        cfg.serve_gen_slots, cfg.serve_gen_max_seq = 8, 32
        cfg.serve_prefill_chunk = 8
        model = build_decoder_lm(
            cfg, _LAYERS, d_model=128, head_dim=32, num_kv_heads=2,
            d_ff=128, vocab_size=64, seq_len=32, window=8, rope=_ROPE,
            gate=True, moe={"num_experts": 8, "k": 2, "d_ff": 128,
                            "shared_d_ff": 128, "routed_scale": 2.5})[0]
        model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
        model.init_layers(seed=0)
        return model

    dec, text = _token_program_text(built(), 8, 32)
    assert "ragged-dot-rows" not in text and "ragged_dot" in text
    assert dec.grouped_product() == {"rows": 0, "library": 2}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gk, "_interpret", lambda: False)
    # the kernel's jitted wrappers were traced for the interpreter above
    # in other tests of this file: lower them afresh
    jax.clear_caches()
    dec, text = _token_program_text(built(), 8, 32)
    assert dec.grouped_product() == {"rows": 2, "library": 0}
    assert "ragged_dot" not in text
    assert text.count('kernel_name = "ragged-dot-rows"') == 2   # up, down
    assert text.count("call @_products") == 4                   # 2 layers x 2
    assert text.count("call @visits") == 2                      # one a layer


def test_a_graph_without_a_mixture_lowers_without_the_kernel(monkeypatch):
    """The post-norm LM the ``gpt1`` cell serves holds no ``MoE`` op: its
    token program is the same text whether or not the backend would take
    the grouped kernel, and names neither product."""
    from flexflow_tpu.models import build_transformer_lm

    def text():
        cfg = ff.FFConfig(batch_size=4, compute_dtype="bfloat16", seed=0)
        cfg.serve_gen_slots = 8
        model = build_transformer_lm(cfg, num_layers=2, d_model=128,
                                     num_heads=2, d_ff=128, seq_len=32,
                                     vocab_size=64)[0]
        model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
        model.init_layers(seed=0)
        dec, out = _token_program_text(model, 8, 32)
        assert dec.grouped_product() == {"rows": 0, "library": 0}
        return out

    plain = text()
    monkeypatch.setattr(gk, "supported", lambda *a, **k: True)
    assert text() == plain
    assert "ragged" not in plain
