"""The repo's own chunk kernel for latent attention
(``ops/latent_chunk_kernel.py``) on the CPU, in Pallas' interpret mode:
parity with ``LatentAttention._over_key_blocks``' loop over the edges of a
chunk's span, the ``supported()`` table, ``chunk_core`` and
``stats()["chunk_attention"]``, the tiny pangu preset served through either
core, and the TPU lowering of a chunk program (lowered only: nothing here
loads the TPU's library)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.op import OpContext, ServeStep
from flexflow_tpu.ops import latent_attention as la
from flexflow_tpu.ops import latent_chunk_kernel as lk
from flexflow_tpu.ops.latent_attention import LatentAttention
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.tensor import Tensor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "tests", "perfbench", "data", "tiny",
                    "tiny-pangu.serve.json")
PAGE = 16

# (bucket B, start, length, pages a slot, keys a block): the chunk's span
# against the blocks' and the pages' edges
CASES = {
    "first_chunk_fills_its_bucket": (16, 0, 16, 6, 32),
    "start_on_a_block_boundary": (64, 64, 64, 10, 32),
    "start_mid_page_after_a_prefix_hit": (64, 37, 50, 8, 32),
    "a_padded_last_chunk_of_three_rows": (8, 5, 3, 6, 32),
    "one_real_row": (16, 48, 1, 6, 32),
    "history_ends_mid_block": (32, 70, 32, 8, 64),
    "one_block_holds_everything": (16, 20, 16, 4, 512),
    "several_blocks_under_a_wide_bucket": (128, 200, 128, 24, 64),
    "the_chunk_spans_three_blocks": (128, 96, 120, 16, 32),
    "the_tables_last_block_is_short_of_pages": (16, 150, 10, 11, 64),
}


def _op(B, heads=4):
    x = Tensor(shape=(1, B, 64), dtype="float32", name="x")
    return LatentAttention("attention_0", x, heads, q_rank=24, kv_rank=32,
                           nope_dim=16, rope_dim=8, v_dim=16, eps=1e-5)


def _chunk(case, dtype):
    """An op, its ``wkv_b``, a pool whose slot's table holds the prompt so
    far in shuffled pages and SENTINELS behind them, and the chunk's
    queries."""
    B, start, length, pps, keys = CASES[case]
    op = _op(B)
    rng = np.random.default_rng(len(case))
    params = {op.w_kvb.name: jnp.asarray(
        rng.standard_normal(op.w_kvb.shape) * 0.3, dtype)}
    num_pages = pps + 3
    pool = rng.standard_normal((num_pages, PAGE, op.row_width))
    pool[..., op.row_values:] = 0
    used = -(-(start + length) // PAGE)
    table = np.full((pps,), num_pages, np.int32)
    table[:used] = rng.permutation(num_pages)[:used]
    q_nope = jnp.asarray(rng.standard_normal((1, B, 4, 16)), dtype)
    q_pe = jnp.asarray(rng.standard_normal((1, B, 4, 8)), dtype)
    where = ServeStep("chunk", jnp.asarray(table), start=jnp.int32(start),
                      length=jnp.int32(length), slot=jnp.int32(0),
                      no_page=num_pages)
    return op, params, jnp.asarray(pool, dtype), where, q_nope, q_pe, keys


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_equals_the_loop_over_key_blocks(monkeypatch, case, dtype,
                                                    tol):
    """Every row of the bucket, padded ones too (both sides read the same
    gathered rows), equals the loop's: the same operands, f32 statistics
    and accumulation on both sides, so float32 agrees to the order of the
    sums and bfloat16 to a rounding of the expanded rows."""
    op, params, pool, where, q_nope, q_pe, keys = _chunk(case, dtype)
    ctx = OpContext(training=False, compute_dtype=dtype, mesh=None)
    monkeypatch.setattr(la, "_KEY_BLOCK", keys)
    want = op._chunk_expanded(params, q_nope, q_pe, pool, where, ctx)
    assert op.chunk_core == {q_nope.shape[1]: "loop"}       # the CPU's
    got = lk.latent_chunk_attention(
        q_nope[0], q_pe[0], pool, where.table, op._kvb(params, ctx),
        where.start, where.length, scale=op.scale, rank=op.kv_rank, keys=keys)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)


def test_blocks_past_the_chunks_last_row_are_never_copied():
    """The rows of every block past ``(start + length - 1) // keys`` are
    NaN in the pool the kernel gets: a chunk costs what the prompt so far
    costs, and a stale page there leaks nothing."""
    B, start, length, pps, keys = 16, 40, 16, 12, 32
    op = _op(B)
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.standard_normal((4, 32, 32)) * 0.3, jnp.float32)
    pool = rng.standard_normal((pps, PAGE, op.row_width)).astype(np.float32)
    pool[..., op.row_values:] = 0
    table = jnp.arange(pps, dtype=jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((B, 4, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((B, 4, 8)), jnp.float32)

    def run(pool):
        return np.asarray(lk.latent_chunk_attention(
            q_nope, q_pe, jnp.asarray(pool), table, w, jnp.int32(start),
            jnp.int32(length), scale=op.scale, rank=32, keys=keys))

    want = run(pool)
    seen = ((start + length - 1) // keys + 1) * keys // PAGE    # pages
    pool[seen:] = np.nan
    got = run(pool)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


_YES = dict(backend="tpu", dtype=jnp.bfloat16, kv_rank=512, row_width=640,
            nope_dim=128, v_dim=128, page_size=16, keys=512)


@pytest.mark.parametrize("change,want", [
    ({}, True),                                 # the cell's op
    ({"page_size": 128}, True),
    ({"kv_rank": 256, "row_width": 384}, True),
    ({"backend": "cpu"}, False),
    ({"backend": "gpu"}, False),
    ({"dtype": jnp.float32}, False),
    ({"dtype": jnp.float16}, False),
    ({"kv_rank": 500}, False),                  # an odd rank
    ({"row_width": 576}, False),                # a bare row, unpadded
    ({"row_width": 512}, False),                # no rotary part behind it
    ({"nope_dim": 96}, False),
    ({"v_dim": 64}, False),
    ({"page_size": 24}, False),                 # pages that tile no block
    ({"distributed": True}, False),             # a mesh
    ({"training": True}, False),                # a gradient
], ids=lambda v: "-".join(f"{k}={getattr(x, '__name__', x)}"
                          for k, x in v.items()) if isinstance(v, dict)
   else str(v))
def test_supported(change, want):
    assert lk.supported(**{**_YES, **change}) is want


def test_the_op_takes_the_loop_on_the_cpu_under_a_mesh_and_in_training(
        monkeypatch):
    """``LatentAttention._chunk_core`` from what the code can see: the
    backend, one dtype for queries and pool, the mesh, the gradient."""
    op = _op(16)
    pool = jnp.zeros((4, PAGE, 640), jnp.bfloat16)
    q = jnp.zeros((1, 16, 4, 128), jnp.bfloat16)
    ctx = OpContext(training=False, compute_dtype="bfloat16", mesh=None)
    op.kv_rank, op.row_width, op.nope_dim, op.v_dim = 512, 640, 128, 128
    assert op._chunk_core(q, pool, ctx) == "loop"               # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert op._chunk_core(q, pool, ctx) == "kernel"
    assert op._chunk_core(q.astype(jnp.float32), pool, ctx) == "loop"
    assert op._chunk_core(q, pool, OpContext(
        training=True, compute_dtype="bfloat16", mesh=None)) == "loop"
    assert op._chunk_core(q, pool, OpContext(
        training=False, compute_dtype="bfloat16",
        mesh=MachineMesh({"n": 2}))) == "loop"
    op.kv_rank = 500
    assert op._chunk_core(q, pool, ctx) == "loop"


# ---- the served graph ----------------------------------------------------
def _tiny_pangu():
    """The tiny pangu preset's model (read, not edited), weights of its
    own from seed 0."""
    from perfbench.families import pangu_moe

    with open(TINY) as f:
        preset = json.load(f)
    model = pangu_moe.build_serve(preset["config"], preset["traffic"])
    model.init_layers(seed=0)
    return model


def test_chunk_core_is_noted_a_program_and_summed_by_the_decoder():
    """``LatentAttention.chunk_core`` holds one entry a chunk bucket traced,
    ``GraphDecoder.chunk_attention()`` sums them over the latent ops; a
    graph without such an op answers ``{}``."""
    from flexflow_tpu.models import build_transformer_lm
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    model = _tiny_pangu()
    ops = [op for op in model.layers if isinstance(op, LatentAttention)]
    assert len(ops) == 2 and all(op.chunk_core == {} for op in ops)
    dec = GraphDecoder(model, 2, 96, prefill_chunk=8)
    assert dec.chunk_attention() == {"latent": {"kernel": 0, "loop": 0}}
    for n, bucket in enumerate((8, 2), 1):
        fn = dec.prefill_fn(bucket)
        (args,) = [a for key, _, f, a in dec._program_specs() if f is fn]
        fn.trace(*args)
        assert dec.chunk_attention() == {"latent": {"kernel": 0,
                                                    "loop": 2 * n}}
    assert ops[0].chunk_core == {8: "loop", 2: "loop"}
    ops[0].chunk_core[8] = "kernel"                     # as a TPU answers
    assert dec.chunk_attention() == {"latent": {"kernel": 1, "loop": 3}}

    cfg = ff.FFConfig(batch_size=4, compute_dtype="float32", seed=0)
    cfg.serve_gen_slots = 2
    plain = build_transformer_lm(cfg, num_layers=1, d_model=32, num_heads=2,
                                 d_ff=32, seq_len=16, vocab_size=32)[0]
    plain.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
    assert GraphDecoder(plain, 2, 16).chunk_attention() == {}


def test_the_tiny_pangu_preset_serves_the_same_tokens_through_either_core(
        monkeypatch):
    """The preset's graph in bfloat16, prompts that pass several pages,
    8-token chunks and (with 32 keys a block) several key blocks, one a
    padded last chunk: the tokens served with every chunk's core FORCED
    through the kernel (the interpreter's) are the loop's, and ``stats()``
    says which core the traced programs got."""
    from flexflow_tpu.serving.generation import GenerationEngine

    rng = np.random.default_rng(42)
    prompts = [rng.integers(1, 2048, n).astype(np.int32)
               for n in (5, 19, 40, 67)]
    monkeypatch.setattr(la, "_KEY_BLOCK", 32)
    served = {}
    for core in ("loop", "kernel"):
        monkeypatch.setattr(LatentAttention, "_chunk_core",
                            lambda *a, c=core: c)
        with GenerationEngine(_tiny_pangu(), slots=2, max_seq=96,
                              prefill_chunk=8) as eng:
            served[core] = [[int(t) for t in s.result(timeout=300)] for s in
                            [eng.submit(p, max_new_tokens=12)
                             for p in prompts]]
            got = eng.stats()["chunk_attention"]["latent"]
        other = "kernel" if core == "loop" else "loop"
        # two latent ops x the buckets 2, 4, 8
        assert got == {core: 6, other: 0}
    assert served["kernel"] == served["loop"]
    assert all(len(out) == 12 for out in served["loop"])


# ---- the lowering for a TPU (lowered, not compiled) ----------------------
def test_the_chunk_program_lowered_for_a_tpu_holds_the_kernel_and_no_loop(
        monkeypatch):
    """A latent decoder of lane-aligned widths in bfloat16, its 16-token
    chunk program lowered for a TPU with the backend's answer steered: each
    latent layer calls ONE traced kernel and the loop over key blocks is
    gone; unsteered, the same program holds the loop and no kernel."""
    from flexflow_tpu.models import build_decoder_lm
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    layers = [{"attention": "latent_attention", "heads": 2, "mlp": "dense"}
              for _ in range(2)]

    def text():
        cfg = ff.FFConfig(batch_size=2, compute_dtype="bfloat16", seed=0)
        cfg.serve_gen_slots, cfg.serve_gen_max_seq = 2, 64
        cfg.serve_prefill_chunk = 16
        model = build_decoder_lm(
            cfg, layers, d_model=128, head_dim=0, num_kv_heads=0, d_ff=128,
            vocab_size=64, seq_len=64, rms_eps=1e-5, sandwich=True,
            latent={"q_rank": 64, "kv_rank": 128, "nope_dim": 128,
                    "rope_dim": 64, "v_dim": 128, "rope_theta": 1e4})[0]
        model.compile(ff.SGDOptimizer(lr=0.01), mesh=MachineMesh({"n": 1}))
        dec = GraphDecoder(model, 2, 64, prefill_chunk=16)
        fn = dec.prefill_fn(16)
        (args,) = [a for key, _, f, a in dec._program_specs() if f is fn]
        return dec, fn.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    dec, plain = text()
    assert dec.chunk_attention() == {"latent": {"kernel": 0, "loop": 2}}
    assert "latent_chunk_attention" not in plain and "while" in plain

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lk, "_interpret", lambda: False)
    jax.clear_caches()      # the wrapper may be traced for the interpreter
    dec, steered = text()
    assert dec.chunk_attention() == {"latent": {"kernel": 2, "loop": 0}}
    assert steered.count('kernel_name = "latent_chunk_attention"') == 1
    assert steered.count("call @latent_chunk_attention") == 2
    assert "stablehlo.while" not in steered
