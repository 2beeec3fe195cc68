#!/usr/bin/env python
"""Benchmark harness — one JSON line per benched model, then a summary line.

Default (no args) sweeps ALL BASELINE.md configs — inception first (the
north-star headline), then alexnet / resnet50 / nmt / transformer / dlrm /
candle_uno — printing one JSON line per model as it completes,
and finally a summary line whose headline fields
(metric/value/unit/vs_baseline) are the Inception numbers and whose
``results`` map carries every model's row.  ``--model X`` benches a single
model and prints one line.

One process holds the chip from start to end: nothing here spawns a child
or probes the backend from outside.  The run FAILS (non-zero exit, no row)
when jax finds no TPU — a CPU timing is never written under a device
metric's name — and when the chip's ``device_kind`` has no entry in the
peaks table below.  The sweep prints an error row for a model that raises
and goes on, but the process exits non-zero if ANY model did not produce
a row.

Measurement matches the reference's fenced timing region
(examples/cpp/AlexNet/alexnet.cc:90-95, 121-126): warm up (compile), then
time ONE window of N steps dispatched asynchronously and ended by
``jax.block_until_ready`` on the last loss (each step consumes the
previous step's donated params, so the last loss waits for the whole
chain).

Input data is device-resident synthetic data, uploaded once before the
timing loop — the reference likewise stages the whole (synthetic) dataset
in zero-copy memory up front (flexflow_dataloader.cc:260-330); real input
pipelines overlap the copy (see flexflow_tpu/data/dataloader.py prefetch).

``vs_baseline`` compares per-chip samples/s against a published-class A100
per-chip figure for the same model (BASELINE.md: the reference repo itself
publishes no numbers; the north star is ">=1x per-chip A100 samples/sec").
"""

import json
import sys
import time

import numpy as np

# A100 per-chip training throughput reference points (public benchmark
# class numbers, mixed precision): used only for the vs_baseline ratio.
A100_SAMPLES_PER_SEC = {
    "inception_v3": 1600.0,
    "alexnet": 5000.0,
    "resnet50": 2900.0,
}

# bf16 peak FLOP/s per chip by device kind (public spec sheets).  A kind
# that is not in the table is an error (_peak), not a null in the row.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}
# HBM bandwidth per chip (bytes/s) — for DLRM's hbm_bw_util row
# (embedding-bound DLRM reports bandwidth utilization, not MFU).
HBM_BW = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}

# internal conv layout for the built models (--conv-layout nchw|nhwc|auto).
# "auto" passes through to the LIBRARY's resolution (op.resolve_conv_layout:
# NHWC on TPU for concat-heavy graphs), so the harness benches exactly what
# fit() runs.
CONV_LAYOUT = "auto"

# --steps-per-dispatch K: fuse K train steps into one dispatched lax.scan
# window (FFConfig.steps_per_dispatch) so the sweep can record
# dispatch-amortized rows alongside the K=1 baseline.
STEPS_PER_DISPATCH = 1

# --flash auto|on|off -> config.flash_attention None/True/False.  The
# round-3 tuning that set auto's s>=1024 threshold timed FORWARD only;
# in training the dense path also pays the O(s^2) score matrix in the
# backward pass, so the crossover for the full step may sit lower.
FLASH = "auto"

# sweep order: headline first so an interrupted sweep still records it
SWEEP = ["inception_v3", "alexnet", "resnet50", "nmt", "transformer",
         "dlrm", "candle_uno"]

# best measured per-chip batch size per workload (v5e, BASELINE.md)
DEFAULT_BATCH = {"inception_v3": 128, "alexnet": 512, "resnet50": 128,
                 "transformer": 32, "nmt": 256, "dlrm": 2048,
                 "candle_uno": 256}


def build(model_name: str, batch_size: int):
    import flexflow_tpu as ff

    rng = np.random.default_rng(0)
    cfg = ff.FFConfig(batch_size=batch_size, compute_dtype="bfloat16")
    cfg.conv_layout = CONV_LAYOUT  # "auto" resolves in the library
    cfg.flash_attention = {"auto": None, "on": True, "off": False}[FLASH]
    cfg.steps_per_dispatch = STEPS_PER_DISPATCH
    if model_name == "inception_v3":
        from flexflow_tpu.models.inception import build_inception_v3
        model, inp, logits = build_inception_v3(cfg, num_classes=1000,
                                                image_size=299)
    elif model_name == "resnet50":
        from flexflow_tpu.models.resnet import build_resnet50
        model, inp, logits = build_resnet50(cfg, num_classes=1000)
    elif model_name == "alexnet":
        from flexflow_tpu.models.alexnet import build_alexnet
        model, inp, logits = build_alexnet(cfg, num_classes=1000)
    elif model_name == "transformer":
        # BERT-base-class encoder (BASELINE.json config 5)
        from flexflow_tpu.models.transformer import build_transformer
        model, inp, logits = build_transformer(
            cfg, num_layers=12, d_model=768, num_heads=12, d_ff=3072,
            seq_len=512, vocab_size=30522, num_classes=2)
    elif model_name == "nmt":
        # reference nmt/nmt.cc:34-44 dims (embed/hidden 2048, vocab 20k)
        from flexflow_tpu.models.nmt import build_nmt
        model, inputs, logits = build_nmt(
            cfg, vocab_size=20000, embed_dim=2048, hidden_dim=2048,
            num_layers=2, src_len=24, tgt_len=24)
        model.compile(ff.SGDOptimizer(lr=0.01),
                      ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                      [], final_tensor=logits)
        model.init_layers(seed=0)
        xs = rng.integers(0, 20000, (batch_size, 24)).astype(np.int32)
        xt = rng.integers(0, 20000, (batch_size, 24)).astype(np.int32)
        y = np.roll(xt, -1, axis=1).astype(np.int32)
        return model, (xs, xt), y
    elif model_name == "dlrm":
        # Criteo-class shape, reference examples/cpp/DLRM/dlrm.cc run
        # scripts: 4x1M tables, 64-dim rows, op-form MSE loss
        from flexflow_tpu.models.dlrm import build_dlrm
        emb = (1000000, 1000000, 1000000, 1000000)
        model, inputs, preds = build_dlrm(
            cfg, embedding_size=emb, sparse_feature_size=64,
            mlp_bot=(256, 512, 64), mlp_top=(320, 512, 256, 1))
        model.compile(ff.SGDOptimizer(lr=0.01), metrics=[],
                      final_tensor=preds)
        model.init_layers(seed=0)
        xs = tuple(rng.integers(0, v, (batch_size, 1)).astype(np.int32)
                   for v in emb)
        dense = rng.standard_normal((batch_size, 256)).astype(np.float32)
        y = rng.random((batch_size, 1)).astype(np.float32)
        return model, xs + (dense,), y
    elif model_name == "candle_uno":
        # reference examples/cpp/candle_uno/candle_uno.cc default towers
        from flexflow_tpu.models.candle_uno import (
            DEFAULT_FEATURE_SHAPES, DEFAULT_INPUT_FEATURES, build_candle_uno)
        model, inputs, preds = build_candle_uno(cfg)
        model.compile(ff.SGDOptimizer(lr=0.001), final_tensor=preds)
        model.init_layers(seed=0)
        xs = tuple(
            rng.standard_normal(
                (batch_size, DEFAULT_FEATURE_SHAPES[kind])).astype(np.float32)
            for kind in DEFAULT_INPUT_FEATURES.values())
        y = rng.random((batch_size, 1)).astype(np.float32)
        return model, xs, y
    else:
        raise ValueError(f"unknown bench model {model_name!r}")
    model.compile(ff.SGDOptimizer(lr=0.01),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [], final_tensor=logits)
    model.init_layers(seed=0)
    shape = inp.shape
    if model_name == "transformer":
        x = rng.integers(0, 30522, shape).astype(np.int32)
        y = rng.integers(0, 2, (shape[0], 1)).astype(np.int32)
    else:
        x = rng.standard_normal(shape, dtype=np.float32)
        y = rng.integers(0, 1000, (shape[0], 1)).astype(np.int32)
    return model, (x,), y


def _error_line(msg, **extra):
    """The one bench_error stdout shape (the last line of stdout parses
    with the summary's headline keys present)."""
    print(json.dumps({"metric": "bench_error", "value": None,
                      "unit": "samples/s/chip", "vs_baseline": None,
                      "error": msg, **extra}), flush=True)


def _require_tpu():
    """No TPU, or a TPU whose peaks are unknown -> no benchmark: fail
    before anything is built."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _error_line(f"no TPU: jax found platform {dev.platform!r} "
                    f"({dev.device_kind}); bench.py measures the chip "
                    f"and does not fall back to another backend")
        raise SystemExit(1)
    _peak(PEAK_FLOPS, dev.device_kind)
    from flexflow_tpu.compile_cache import enable
    enable()


def _peak(table, kind):
    """The per-chip peak for ``kind``; an unknown device kind is an
    error, never a default and never a null in the row."""
    if kind not in table:
        raise ValueError(
            f"device_kind {kind!r} has no entry in bench.py's peaks "
            f"table (known: {sorted(table)}); add its published peak "
            f"with a source before benchmarking on it")
    return table[kind]


def _hbm_bytes_per_step(model, batch_size, n_chips):
    """Analytic per-chip HBM traffic per training step for bandwidth-bound
    models (DLRM): embedding rows move ~3x (fwd gather read, update
    scatter read+write) over the chip's batch shard, and every DENSE
    parameter moves ~4x (fwd read, bwd-grad write, optimizer read+write)
    at FULL size — weights are replicated under data parallelism, so
    every chip streams the whole f32 set.  Tables on the sparse-update
    path (FFConfig.sparse_embedding_updates — the default for DLRM's
    plain SGD) are NOT streamed in full: only their gathered rows move,
    so they are excluded from the dense-parameter term.  Activations
    are small next to both here."""
    sparse_tables = {t for _, t, _ in model._sparse_embedding_specs()}
    emb = 0
    params = 0
    for op in model.layers:
        kind = type(op).__name__.lower()
        if "embedding" in kind:
            out = op.outputs[0]
            width = int(np.prod(out.shape[1:]))
            emb += 3 * batch_size * width * 4  # f32 table rows
        for w in getattr(op, "weights", []) or []:
            if w.name in sparse_tables:
                continue  # rows counted above; the table never streams
            params += 4 * int(np.prod(w.shape)) * 4  # f32 params
    return emb / max(1, n_chips) + params


def bench_model(model_name, batch_size, iters):
    import jax

    batch_size = batch_size or DEFAULT_BATCH.get(model_name, 128)
    model, xs, y = build(model_name, batch_size)
    n_chips = len(jax.devices())
    # device-resident batch, pre-sharded over the mesh (uploaded once;
    # see module docstring)
    batch = model._shard_batch(tuple(xs) + (y,))
    jax.block_until_ready(batch)

    # --steps-per-dispatch K: each timed call dispatches ONE fused K-step
    # window over the same device-resident batch stacked K times (the
    # dispatch-amortized path fit() runs at steps_per_dispatch=K); the
    # samples/s denominator scales by K below via steps_per_call
    k = STEPS_PER_DISPATCH
    steps_per_call = k
    if k > 1:
        import jax.numpy as jnp
        window = tuple(jnp.stack([a] * k) for a in batch)
        jax.block_until_ready(window)

        def one_call():
            losses, _ = model.train_window(window)
            return losses[-1]
    else:
        def one_call():
            return model.train_batch(*batch)

    kind = jax.devices()[0].device_kind
    peak = _peak(PEAK_FLOPS, kind)

    # warmup / compile
    for _ in range(3):
        loss = one_call()
    jax.block_until_ready(loss)

    # one timed window: N async dispatches ended by block_until_ready on
    # the last loss (donated params chain every step behind it)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = one_call()
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    final_loss = float(loss)
    if not np.isfinite(final_loss):
        raise FloatingPointError(f"{model_name}: loss {final_loss}")

    sps = batch_size * iters * steps_per_call / dt
    per_chip = sps / max(1, n_chips)
    base = A100_SAMPLES_PER_SEC.get(model_name)
    # fwd FLOPs from the op-level analytic model; training step ~= 3x fwd
    # (bwd-data + bwd-filter each ~1x fwd for conv/matmul ops)
    fwd_flops = sum(op.flops() for op in model.layers)
    step_flops = 3 * fwd_flops
    achieved = step_flops * iters * steps_per_call / dt / max(1, n_chips)
    row = {
        "metric": f"{model_name}_train_samples_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "samples/s/chip",
        "vs_baseline": round(per_chip / base, 4) if base else None,
        "ms_per_step": round(dt / (iters * steps_per_call) * 1e3, 2),
        "steps_per_dispatch": k,
        "tflops_per_chip": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4),
        "batch_size": batch_size,
        "loss": round(final_loss, 4),
        "conv_layout": getattr(model, "resolved_conv_layout",
                               model.config.conv_layout),
    }
    if model_name == "dlrm":
        bytes_step = _hbm_bytes_per_step(model, batch_size, n_chips)
        row["hbm_bw_util"] = round(
            bytes_step * iters / dt / _peak(HBM_BW, kind), 4)
    return row


def main(argv=None):
    global CONV_LAYOUT, FLASH, STEPS_PER_DISPATCH
    model_name = None  # default: full sweep
    batch_size = 0
    iters = 20
    budget_s = 1500.0
    sweep = SWEEP
    args = list(sys.argv[1:] if argv is None else argv)

    def _val(i, flag):
        if i + 1 >= len(args):  # a malformed invocation must still
            # produce a structured line, not a bare traceback
            _error_line(f"missing value for {flag}")
            raise SystemExit(2)
        return args[i + 1]

    for i, a in enumerate(args):
        if a == "--model":
            model_name = _val(i, a)
        if a == "--batch":
            batch_size = int(_val(i, a))
        if a == "--iters":
            iters = int(_val(i, a))
        if a == "--budget":
            budget_s = float(_val(i, a))
        if a == "--models":  # subset sweep
            sweep = _val(i, a).split(",")
        if a == "--conv-layout":
            CONV_LAYOUT = _val(i, a).lower()
        if a == "--flash":
            FLASH = _val(i, a).lower()
            if FLASH not in ("auto", "on", "off"):
                _error_line(f"--flash must be auto|on|off, got {FLASH!r}")
                raise SystemExit(2)
        if a == "--steps-per-dispatch":
            STEPS_PER_DISPATCH = max(1, int(_val(i, a)))
    if "--all" in args or model_name == "all":
        model_name = None

    _require_tpu()
    if model_name:  # single-model mode: a failure is a traceback
        print(json.dumps(bench_model(model_name, batch_size, iters)),
              flush=True)
        return
    summary = run_sweep(sweep, batch_size, iters, budget_s)
    if summary["models_ok"] != summary["models_total"]:
        raise SystemExit(1)


def run_sweep(sweep, batch_size=0, iters=20, budget_s=1500.0,
              _bench=None):
    """The --all loop: one JSON line per model as it completes, then the
    summary line.  A model that raises gets an error row and the sweep
    goes on (the record shows every model's outcome), but ``models_ok``
    then falls short of ``models_total`` and main() exits non-zero.
    ``_bench`` is the per-model bench function (tests inject a fake;
    default bench_model)."""
    _bench = _bench or bench_model
    t_start = time.perf_counter()
    results = {}
    ok = 0
    for name in sweep:
        if time.perf_counter() - t_start > budget_s:
            results[name] = {"skipped": f"time budget {budget_s}s exceeded"}
            continue
        try:
            row = _bench(name, batch_size, iters)
            results[name] = row
            ok += 1
            print(json.dumps(row), flush=True)
        except Exception as e:  # noqa: BLE001 — boundary: recorded as
            # an error row, and main() turns it into a non-zero exit
            results[name] = {"error": f"{type(e).__name__}: {e}"[:400]}
            print(json.dumps({"metric": name, "error": results[name]["error"]
                              }), flush=True)
    head = results.get("inception_v3", {})
    compact = {}
    for name, row in results.items():
        if "error" in row or "skipped" in row:
            compact[name] = row
        else:
            compact[name] = {k: row[k] for k in
                             ("value", "ms_per_step", "tflops_per_chip",
                              "mfu", "vs_baseline", "batch_size",
                              "hbm_bw_util", "qps_requests",
                              "speedup_vs_naive", "p50_ms", "p99_ms")
                             if row.get(k) is not None}
    summary = {
        "metric": head.get("metric", "bench_sweep"),
        "value": head.get("value"),
        "unit": "samples/s/chip",
        "vs_baseline": head.get("vs_baseline"),
        "mfu": head.get("mfu"),
        "models_ok": ok,
        "models_total": len(sweep),
        "results": compact,
    }
    print(json.dumps(summary), flush=True)
    return summary


if __name__ == "__main__":
    main()
