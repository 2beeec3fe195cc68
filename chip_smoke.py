#!/usr/bin/env python3
"""First contact with the chip: the quickest proof that the system still
starts where it is measured.

    python chip_smoke.py                 # on a machine that holds a TPU

One process, no children.  Through the entry points a user would call it

1. trains BERT-base (12 layers, d_model 768, 12 heads, d_ff 3072, seq 512,
   vocab 30 522, bf16 compute, Adam) with ``flexflow_tpu.cli`` running
   ``examples/apps/transformer.py`` at ``-b 32`` for 8 optimizer steps on
   one chip; shows the Mosaic flash-attention custom call in the lowered
   step; checks every loss is finite, that the loss falls on a repeated
   batch, and that the first two steps agree with a dense-attention
   (``flash_attention=False``) model from the same seed;
2. serves the 12-layer / 768-wide / seq-1024 / vocab-50 257 causal LM
   through ``GenerationEngine``: mixed-length prompts with a shared prefix,
   greedy, 32 new tokens each, every token checked against ``model.predict``;
3. trains InceptionV3 at ``-b 128`` through the same CLI (NHWC auto layout,
   the tuned table keyed by this chip's ``device_kind``);
4. compiles the off-by-default Pallas LayerNorm kernel with Mosaic and
   compares it with its jnp reference;
5. when four chips are visible: BERT-base again under ``-ll:tpu 4`` as pure
   data parallel, a hand ``n=2 x c=2`` strategy and a searched-then-executed
   one, each checked for placement on four distinct chips, no replicate
   fallback (FF106) and a first-step loss that matches a one-chip
   evaluation; then the ``__graft_entry__.multichip_patterns`` at tiny shapes
   on the real chips.

No leg is inside a try/except: a leg that fails is a traceback and a
non-zero exit.  Without a TPU the script exits non-zero before it builds
anything and prints no result.  The last line of stdout of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Timings printed on the way are for orientation — compile separated from
steady state, the device stamped on every line.  They are not a benchmark.

``--rehearse-cpu`` is the explicit rehearsal switch for a sandbox without a
chip: the same legs at tiny sizes on XLA:CPU (depth, batch and the serving
widths cut; Pallas in interpret mode; no flash kernel, so the kernel-proof
assertions are skipped).  Every line then starts with ``platform=cpu`` and
the last line is not the pass line above.
"""

import gc
import io
import json
import os
import re
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# Full-size legs (the chip) and the CPU rehearsal's cuts.  Width of the
# trained model is never cut: examples/apps/transformer.py fixes it.
SIZES = {
    "chip": dict(
        bert_layers=12, bert_batch=32, bert_batch_4chip=128,
        lm=dict(num_layers=12, d_model=768, num_heads=12, d_ff=3072,
                seq_len=1024, vocab_size=50257),
        lm_new_tokens=32, lm_prefix=64, lm_tails=(9, 40, 150, 23, 77, 5),
        inception_batch=128, inception_epochs=3,
        norm_rows=32 * 512, search_budget=40),
    "cpu": dict(
        bert_layers=1, bert_batch=4, bert_batch_4chip=8,
        lm=dict(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                seq_len=128, vocab_size=211),
        lm_new_tokens=8, lm_prefix=32, lm_tails=(3, 9, 30, 5, 17, 2),
        inception_batch=2, inception_epochs=1,
        norm_rows=64, search_budget=10),
}

# Stated tolerances (bf16 compute: 8 significant bits, 2**-8 per rounding).
#  * two training paths through the same bf16 model (flash vs dense
#    attention, one chip vs four) differ by rounding order only; the loss
#    is a mean over the batch of O(1) values
LOSS_RTOL = 2e-2
#  * a served token must be within this many nats of the reference's own
#    argmax at that position (~8 bf16 roundings of an O(1) logit); a wrong
#    cache row or position lands ~0.5 nats away on this random-weight model
TOKEN_LOGPROB_TOL = 0.03
#  * the LayerNorm kernel keeps f32 statistics like its reference
NORM_ATOL = 2e-5


class _Stamped(io.TextIOBase):
    """Prefix every line written to stdout with the device stamp."""

    def __init__(self, raw, stamp):
        self.raw, self.stamp, self._bol = raw, stamp, True

    def write(self, s):
        for part in s.splitlines(keepends=True):
            if self._bol:
                self.raw.write(self.stamp)
            self.raw.write(part)
            self._bol = part.endswith("\n")
        return len(s)

    def flush(self):
        self.raw.flush()


class _CompileStats:
    """Backend compile seconds and persistent-cache traffic, from jax's
    own monitoring events, so each leg can report them as a delta."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.writes = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.writes += 1  # recorded when a NEW entry is written

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self):
        return (self.hits, self.writes, self.compile_s)

    def since(self, snap):
        return (f"backend-compile {self.compile_s - snap[2]:.1f} s, "
                f"cache hits {self.hits - snap[0]}, "
                f"new entries {self.writes - snap[1]}")


class _PallasWatch:
    """Record the ``interpret`` argument of every pallas_call traced in
    this process (the repo's kernels and jax's flash kernel all reach it
    as ``pl.pallas_call``)."""

    def __init__(self):
        import jax.experimental.pallas as pl
        self.calls = []
        real = pl.pallas_call

        def watched(*args, **kwargs):
            self.calls.append(bool(kwargs.get("interpret", False)))
            return real(*args, **kwargs)

        pl.pallas_call = watched


def _cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _finite(losses, what):
    losses = np.asarray(losses, np.float64)
    assert losses.size and np.all(np.isfinite(losses)), (what, losses)
    return losses


def _close(a, b, what, rtol=LOSS_RTOL):
    assert abs(a - b) <= rtol * max(abs(a), abs(b)), (
        f"{what}: {a:.6f} vs {b:.6f} differ by more than rtol {rtol}")


def _bert_data(batch, seed=0):
    """The dataset examples/apps/transformer.py draws for ``-b batch
    --seed seed`` (same generator, same order)."""
    rng = np.random.default_rng(seed)
    n = batch * 8
    x = rng.integers(0, 30522, (n, 512)).astype(np.int32)
    y = rng.integers(0, 2, (n, 1)).astype(np.int32)
    return x, y


def _build_bert(layers, batch, flash=None):
    """The app's model, built directly on ONE chip whatever the host
    holds (for the dense-attention arm and the one-chip reference)."""
    import flexflow_tpu as ff
    from flexflow_tpu.models.transformer import build_transformer

    cfg = ff.FFConfig(batch_size=batch, seed=0)
    cfg.flash_attention = flash
    model, _, logits = build_transformer(
        cfg, num_layers=layers, d_model=768, num_heads=12, d_ff=3072,
        seq_len=512, vocab_size=30522, num_classes=2)
    model.compile(ff.AdamOptimizer(alpha=1e-4),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.METRICS_ACCURACY], final_tensor=logits,
                  mesh=ff.MachineMesh({"n": 1}))
    model.init_layers(seed=0)
    return model


def _run_app(script, flags):
    """``flexflow-tpu <script> <flags>`` in this process; returns the
    model the app trained."""
    from flexflow_tpu import cli

    return cli.run_script(
        [os.path.join(REPO, "examples", "apps", script)] + flags)["model"]


def _step_text(model, batch):
    """Lowered (StableHLO) text of the model's jitted train step."""
    placed = tuple(model._shard_batch(batch))
    return model._train_step.lower(model._params, model._opt_state,
                                   placed, model._step).as_text()


def _flash_operand_batches(text):
    """Leading (batch) dim of the first operand of every Mosaic custom
    call in a lowered step (the op's type signature ends its line)."""
    out = []
    for line in text.splitlines():
        if "@tpu_custom_call" in line:
            m = re.match(r"tensor<(\d+)x", line.rsplit(": (", 1)[-1])
            out.append(int(m.group(1)) if m else -1)
    return out


# ---------------------------------------------------------------------------
# leg 1: train BERT-base on one chip through the CLI
# ---------------------------------------------------------------------------
def leg_train_bert(sz, on_tpu, stats):
    import jax

    layers, batch = sz["bert_layers"], sz["bert_batch"]
    snap, t0 = stats.snapshot(), time.perf_counter()
    model = _run_app("transformer.py",
                     ["-b", str(batch), "-e", "1", "--seed", "0",
                      "-ll:tpu", "1", "--num-layers", str(layers)])
    wall = time.perf_counter() - t0
    cli_losses = _finite(model.last_epoch_losses, "bert cli losses")
    assert cli_losses.size == 8 and model._step == 8, cli_losses
    assert model.mesh.num_devices == 1, model.mesh
    print(f"bert: cli run, 8 optimizer steps incl. compile: {wall:.1f} s "
          f"({stats.since(snap)}); losses "
          f"{np.array2string(cli_losses, precision=4)}")

    x, y = _bert_data(batch)
    first = (x[:batch], y[:batch])
    if on_tpu:
        # the kernel is in the program that ran, not assumed to be
        from flexflow_tpu.ops.attention import MultiHeadAttention
        assert model.config.flash_attention is None  # auto selected it
        n_attn = sum(isinstance(op, MultiHeadAttention)
                     for op in model.layers)
        calls = _flash_operand_batches(_step_text(model, first))
        # jit shares one lowered function among the equal-shaped layers:
        # two sites = the owned kernel's forward and its fused backward
        # (ops/flash_kernel.py; at s = 512 the keys fit one block)
        assert len(calls) >= 2 and set(calls) == {batch}, (
            f"expected the Mosaic flash kernel (fwd, fused bwd) in the "
            f"lowered step, found tpu_custom_call batches {calls}")
        assert model.attention_kernels()["owned"] == n_attn, \
            model.attention_kernels()
        print(f"bert: lowered train step holds {len(calls)} "
              f"tpu_custom_call (Mosaic) sites — flash fwd, fused bwd — "
              f"shared by {n_attn} attention ops, all on the owned kernel")

    # steady state + memorisation: 8 more steps on ONE repeated batch
    times, rep = [], []
    for _ in range(8):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(model.train_batch(*first))
        times.append((time.perf_counter() - t0) * 1e3)
        rep.append(float(loss))
    rep = _finite(rep, "bert repeated-batch losses")
    assert rep[-1] < rep[0], f"loss did not fall on a repeated batch: {rep}"
    print(f"bert: repeated batch, loss {rep[0]:.4f} -> {rep[-1]:.4f}; "
          f"steady state {np.median(times):.1f} ms/step (median of 8, each "
          f"ended by block_until_ready; orientation, not a benchmark)")

    # the flash kernel's numerical check: a dense-attention model from the
    # same seed takes the same first two steps (the second depends on the
    # first's gradients, so it checks the backward kernel too)
    snap, t0 = stats.snapshot(), time.perf_counter()
    dense = _build_bert(layers, batch, flash=False)
    d0 = float(jax.block_until_ready(dense.train_batch(*first)))
    first_step_s = time.perf_counter() - t0
    d1 = float(dense.train_batch(x[batch:2 * batch], y[batch:2 * batch]))
    print(f"bert: dense-attention arm, build to first step "
          f"{first_step_s:.1f} s ({stats.since(snap)}); losses "
          f"{d0:.4f} {d1:.4f} vs the cli run's {cli_losses[0]:.4f} "
          f"{cli_losses[1]:.4f} (rtol {LOSS_RTOL})")
    if on_tpu:
        assert not _flash_operand_batches(_step_text(dense, first))
    _close(cli_losses[0], d0, "flash vs dense, step 1")
    _close(cli_losses[1], d1, "flash vs dense, step 2")


# ---------------------------------------------------------------------------
# leg 2: serve the 12-layer LM through GenerationEngine
# ---------------------------------------------------------------------------
def leg_serve_lm(sz, stats):
    import flexflow_tpu as ff
    from flexflow_tpu.models.transformer import build_transformer_lm

    dims, new = sz["lm"], sz["lm_new_tokens"]
    seq, vocab = dims["seq_len"], dims["vocab_size"]
    snap, t0 = stats.snapshot(), time.perf_counter()
    cfg = ff.FFConfig(batch_size=2, seed=0)
    model = build_transformer_lm(cfg, **dims)[0]
    model.compile(ff.SGDOptimizer(lr=0.01),
                  mesh=ff.MachineMesh({"n": 1}))
    model.init_layers(seed=0)

    rng = np.random.default_rng(7)
    prefix = rng.integers(1, vocab, sz["lm_prefix"])
    prompts = [np.concatenate([prefix, rng.integers(1, vocab, t)])
               .astype(np.int32) for t in sz["lm_tails"]]
    eng = ff.GenerationEngine(model, slots=4, max_new_tokens=new)
    with eng:
        build_s = time.perf_counter() - t0
        # the first request alone, so its prefix pages are in the cache
        # when the rest arrive together and share them
        t1 = time.perf_counter()
        outs = [[int(t) for t in eng.submit(prompts[0], max_new_tokens=new)
                 .result(timeout=900)]]
        first_s = time.perf_counter() - t1
        t1 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=new) for p in prompts[1:]]
        outs += [[int(t) for t in s.result(timeout=900)] for s in streams]
        rest_s = time.perf_counter() - t1
        snap_eng = eng.stats()  # read INSIDE the with
    assert [len(o) for o in outs] == [new] * len(prompts), \
        [len(o) for o in outs]
    assert snap_eng["prefix_hit_tokens"] > 0, snap_eng
    # every stream gave its pages back; what stays is the prefix cache's
    cached = snap_eng["prefix_pages_cached"]
    assert eng._pool.pages_in_use == cached, (eng._pool.pages_in_use, cached)
    print(f"serve: {len(prompts)} streams x {new} tokens, slots=4; model "
          f"+ engine up in {build_s:.1f} s, first request {first_s:.2f} s, "
          f"the other {len(prompts) - 1} together {rest_s:.2f} s "
          f"({stats.since(snap)}; orientation, not a benchmark); "
          f"prefix_hit_tokens "
          f"{snap_eng['prefix_hit_tokens']}, pages in use after retire "
          f"{cached} (all {cached} held by the prefix cache)")

    # every served token against model.predict: one causal forward over
    # prompt + served tokens gives the reference distribution at every
    # position (teacher-forced — identical to the argmax loop up to the
    # first difference, and it goes on checking after it)
    exact = total = 0
    worst = 0.0
    for prompt, out in zip(prompts, outs):
        padded = np.zeros((1, seq), np.int32)
        full = np.concatenate([prompt, out])
        padded[0, :len(full)] = full
        at = len(prompt) - 1  # the position that predicts out[0]
        probs = np.asarray(model.predict([padded], batch_size=2)[0]
                           [at:at + len(out)], np.float64)
        for i, tok in enumerate(out):
            row = np.log(probs[i] + 1e-30)
            gap = float(row.max() - row[tok])
            if gap > worst:
                worst = gap
            if gap > 0.0:
                print(f"serve: stream of prompt len {len(prompt)} differs "
                      f"from the reference argmax at new token {i}: "
                      f"reference log-prob gap {gap:.5f} nats")
            exact += gap == 0.0
            total += 1
    print(f"serve: {exact}/{total} served tokens are the reference argmax; "
          f"worst reference gap {worst:.5f} nats "
          f"(tolerance {TOKEN_LOGPROB_TOL})")
    assert worst <= TOKEN_LOGPROB_TOL, worst


# ---------------------------------------------------------------------------
# leg 3: InceptionV3 through the CLI (the second op family)
# ---------------------------------------------------------------------------
def leg_train_inception(sz, on_tpu, stats):
    import jax

    from flexflow_tpu import tuned

    snap, t0 = stats.snapshot(), time.perf_counter()
    model = _run_app("inception.py",
                     ["-b", str(sz["inception_batch"]), "-e",
                      str(sz["inception_epochs"]), "--seed", "0",
                      "-ll:tpu", "1"])
    wall = time.perf_counter() - t0
    losses = _finite(model.last_epoch_losses, "inception losses")
    kind = jax.devices()[0].device_kind
    print(f"inception: cli run, {model._step} steps at b"
          f"{sz['inception_batch']} incl. compile: {wall:.1f} s "
          f"({stats.since(snap)}); conv layout "
          f"{model.resolved_conv_layout}; last-epoch losses "
          f"{np.array2string(losses, precision=4)}")
    if on_tpu:
        assert model.resolved_conv_layout == "nhwc", \
            model.resolved_conv_layout
        table = tuned._tuned_table()
        assert kind in table["fast_concat"], (
            f"tuned_defaults.json has no entry for device_kind {kind!r}: "
            f"{sorted(table['fast_concat'])}")
        print(f"inception: tuned table key matches device_kind {kind!r} "
              f"(fast_concat={table['fast_concat'][kind]})")


# ---------------------------------------------------------------------------
# leg 4: the off-by-default Pallas kernel compiles and agrees
# ---------------------------------------------------------------------------
def leg_pallas_kernels(sz):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_norm

    rng = np.random.default_rng(11)
    rows, d = sz["norm_rows"], 768
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.bfloat16)
    res = jnp.asarray(rng.standard_normal((rows, d)), jnp.bfloat16)
    scale = jnp.asarray(rng.standard_normal(d), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(d), jnp.float32)
    assert pallas_norm.supported(x.shape, x.dtype)
    got = jax.jit(lambda *a: pallas_norm.fused_layernorm(*a, 1e-5))(
        x, res, scale, bias)
    want = jax.jit(lambda *a: pallas_norm._ln_reference(*a, 1e-5))(
        x, res, scale, bias)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"pallas_norm: LayerNorm+residual over {rows} x {d} bf16 rows, "
          f"max |kernel - reference| {err:.2e} (atol {NORM_ATOL})")
    assert err <= NORM_ATOL, err


# ---------------------------------------------------------------------------
# leg 5: four chips
# ---------------------------------------------------------------------------
def _assert_spread(arr, what, expect_split):
    """``arr`` lives on four distinct chips; when ``expect_split`` each
    chip holds less than the whole."""
    shards = arr.addressable_shards
    ids = sorted({s.device.id for s in shards})
    assert len(ids) == 4, f"{what}: on devices {ids}"
    shapes = {tuple(s.data.shape) for s in shards}
    if expect_split:
        assert all(int(np.prod(sh)) < int(np.prod(arr.shape))
                   for sh in shapes), (
            f"{what}: shard shapes {shapes} of full {arr.shape}")
    return ids, shapes


def _hand_n2c2(layers):
    """Tensor-parallel attention heads and FFN channels over c=2, batch
    over n=2 (the __graft_entry__ ring/TP pattern at BERT-base names)."""
    from flexflow_tpu.config import ParallelConfig
    pc = ParallelConfig(dims=(2, 1, 2), device_ids=(0, 1, 2, 3))
    return {f"{name}_{i}": pc for i in range(layers)
            for name in ("attention", "ffn_up")}


def leg_four_chips(sz, on_tpu, stats):
    import jax

    import __graft_entry__ as graft
    from flexflow_tpu.strategy.proto import save_strategy_file

    layers, batch = sz["bert_layers"], sz["bert_batch_4chip"]
    x, y = _bert_data(batch)
    first = (x[:batch], y[:batch])

    # one-chip reference for every arm: the loss of the first batch at the
    # seed's initial weights (the first step's loss is computed before its
    # update), evaluated on ONE device in quarter batches
    ref_model = _build_bert(layers, batch // 4, flash=False)
    ref, _ = ref_model.evaluate(first[0], first[1], batch_size=batch // 4)
    print(f"4chip: one-chip reference loss of the first batch {ref:.4f}")
    del ref_model
    gc.collect()

    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    hand_pb = os.path.join(out_dir, "bert_n2c2.pb")
    save_strategy_file(hand_pb, _hand_n2c2(layers))
    arms = [
        ("data-parallel n=4", []),
        ("hand n=2 x c=2", ["-import", hand_pb]),
        ("searched (hybrid, analytic)",
         ["--budget", str(sz["search_budget"]), "--search-mode", "hybrid",
          "--cost-estimator", "analytic"]),
    ]
    all_losses = {}
    for name, extra in arms:
        snap, t0 = stats.snapshot(), time.perf_counter()
        model = _run_app("transformer.py",
                         ["-b", str(batch), "-e", "1", "--seed", "0",
                          "-ll:tpu", "4", "--num-layers", str(layers)]
                         + extra)
        wall = time.perf_counter() - t0
        losses = _finite(model.last_epoch_losses, name)
        assert losses.size == 8, losses
        live = {a: s for a, s in model.mesh.sizes.items() if s > 1}
        assert model.mesh.num_devices == 4, model.mesh
        assert not model.runtime_fallback_sites, \
            sorted(model.runtime_fallback_sites)
        placed = model._shard_batch(first)[0]
        tp = model._params["ffn_up_0/kernel"]
        pc = model.config.strategies.get("ffn_up_0")
        ids, shapes = _assert_spread(
            placed, f"{name}: batch", expect_split="n" in live or "s" in live)
        _, wshapes = _assert_spread(
            tp, f"{name}: ffn_up_0/kernel",
            expect_split=pc is not None and pc.dims[-1] > 1)
        if name.startswith("hand"):
            assert live == {"n": 2, "c": 2}, live
        if on_tpu and model.config.flash_attention is None \
                and "s" not in live:
            # the flash kernel runs per shard, not on a gathered batch
            calls = _flash_operand_batches(_step_text(model, first))
            want = batch // live.get("n", 1)
            assert calls and set(calls) == {want}, (calls, want)
        t1 = time.perf_counter()
        jax.block_until_ready(model.train_batch(*first))
        step_ms = (time.perf_counter() - t1) * 1e3
        print(f"4chip: {name}: mesh {live}, devices {ids}, batch shards "
              f"{sorted(shapes)} of {tuple(placed.shape)}, ffn_up_0/kernel "
              f"shards {sorted(wshapes)} of {tuple(tp.shape)}, FF106 sites "
              f"0; 8 steps incl. compile {wall:.1f} s ({stats.since(snap)})"
              f", one more step {step_ms:.1f} ms (orientation, not a "
              f"benchmark); first loss {losses[0]:.4f} vs one-chip "
              f"{ref:.4f}")
        _close(float(losses[0]), ref, f"{name}: first-step loss vs one chip")
        all_losses[name] = losses
        del model, placed, tp  # free the chips before the next arm builds
        gc.collect()
    # the three arms do the same mathematics: they must agree at EVERY
    # step, which checks the tensor-parallel and searched backward passes
    # against the data-parallel one
    base = all_losses["data-parallel n=4"]
    for name, losses in all_losses.items():
        for i, (a, b) in enumerate(zip(losses, base)):
            _close(float(a), float(b), f"{name} vs data-parallel, step {i}")
    print("4chip: the three arms agree at all 8 steps "
          f"(rtol {LOSS_RTOL})")

    losses = graft.multichip_patterns(4)
    assert len(losses) == 6, sorted(losses)  # composed needs 8 devices
    print(f"4chip: {len(losses)} multichip patterns ran on "
          f"{jax.devices()[0].platform}: "
          + ", ".join(f"{k} {v:.4f}" for k, v in losses.items()))


# ---------------------------------------------------------------------------
def main(argv):
    rehearse = "--rehearse-cpu" in argv
    unknown = [a for a in argv if a != "--rehearse-cpu"]
    if unknown:
        raise SystemExit(f"chip_smoke.py: unknown arguments {unknown} "
                         f"(the only switch is --rehearse-cpu)")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jaxlib

    dev = jax.devices()[0]
    count = len(jax.devices())
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    print(f"platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={count} jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu_version}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not (rehearse and dev.platform == "cpu"):
        raise SystemExit(
            f"chip_smoke.py: no TPU — jax found platform {dev.platform!r} "
            f"({dev.device_kind}); nothing was run")

    raw = sys.stdout
    sys.stdout = _Stamped(
        raw, f"[{dev.platform}:{dev.device_kind} x{count}] " if on_tpu
        else "platform=cpu ")
    t_all = time.perf_counter()
    sz = SIZES["chip" if on_tpu else "cpu"]
    sys.path.insert(0, REPO)
    from flexflow_tpu.compile_cache import enable as enable_compile_cache
    cache_dir = enable_compile_cache()
    before = _cache_entries(cache_dir)
    print(f"compile cache: {cache_dir} ({before} entries; "
          f"JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    stats, pallas = _CompileStats(), _PallasWatch()

    legs = [lambda: leg_train_bert(sz, on_tpu, stats),
            lambda: leg_serve_lm(sz, stats),
            lambda: leg_train_inception(sz, on_tpu, stats),
            lambda: leg_pallas_kernels(sz)]
    if count == 4:
        legs.append(lambda: leg_four_chips(sz, on_tpu, stats))
    for leg in legs:
        leg()
        gc.collect()  # the leg's models are unreachable: free the chip
    if count != 4:
        print(f"4chip: leg not run ({count} device(s) visible)")

    if on_tpu:
        assert pallas.calls and not any(pallas.calls), pallas.calls
    print(f"pallas_call sites traced: {len(pallas.calls)}, with "
          f"interpret=True: {sum(pallas.calls)}")
    after = _cache_entries(cache_dir)
    print(f"compile cache: {cache_dir} now {after} entries "
          f"(+{after - before}); whole run {time.perf_counter() - t_all:.0f} s")
    sys.stdout.flush()
    sys.stdout = raw
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count}
    if on_tpu:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    else:
        print("platform=cpu " + json.dumps(
            {"rehearsal_ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
