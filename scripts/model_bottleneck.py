#!/usr/bin/env python
"""Where does a bench workload's step time go?  (VERDICT r3 #2 analysis)

Profiles the ops of a bench.py model graph in isolation on the attached
chip (profiling.profile_op — the calibrated slope-timing path), DEDUPED
by (op type, shapes, hyperparams) so each unique configuration compiles
once (a naive all-ops inception sweep is ~190 compiles x2).
Aggregates fwd+bwd per op TYPE;
the per-op sum excludes XLA's cross-op fusion, so sum > end-to-end
bench time is expected — the per-type shares say which op class to
attack.

Run on the bench chip:
    python scripts/model_bottleneck.py [--model inception_v3] \
        [--layout nhwc] [--flash auto|on|off] [--batch N] [--top 25]
"""

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def op_key(op):
    return (op.op_type.value,
            tuple(t.shape for t in op.inputs),
            tuple(t.shape for t in op.outputs),
            tuple(w.shape for w in op.weights),
            getattr(op, "stride", None), getattr(op, "kernel", None),
            getattr(op, "groups", None), getattr(op, "activation", None),
            getattr(op, "pool_type", None), getattr(op, "causal", None))


def main():
    import bench
    from flexflow_tpu.compile_cache import enable as _enable_cache
    _enable_cache()

    model_name = "inception_v3"
    layout = None  # default: bench.py's per-model best
    top = 25
    batch = 0
    args = sys.argv[1:]

    def _val(i, flag):
        if i + 1 >= len(args):
            raise SystemExit(f"usage: missing value for {flag}")
        return args[i + 1]

    for i, a in enumerate(args):
        if a == "--model":
            model_name = _val(i, a)
        if a == "--layout":
            layout = _val(i, a)
        if a == "--top":
            top = int(_val(i, a))
        if a == "--batch":
            batch = int(_val(i, a))
        if a == "--flash":
            v = _val(i, a).lower()
            if v not in ("auto", "on", "off"):
                raise SystemExit(f"--flash must be auto|on|off, got {v!r}")
            bench.FLASH = v

    bench._require_tpu()

    if layout:
        bench.CONV_LAYOUT = layout
    batch = batch or bench.DEFAULT_BATCH.get(model_name, 128)
    model, _, _ = bench.build(model_name, batch)
    layout = model.config.conv_layout
    flash = model.config.flash_attention

    from flexflow_tpu.profiling import profile_op

    groups = {}
    for op in model.layers:
        groups.setdefault(op_key(op), []).append(op)
    print(f"{len(model.layers)} ops -> {len(groups)} unique shapes",
          flush=True)

    by_type = defaultdict(float)
    rows = []
    failed = []
    for i, ops in enumerate(groups.values()):
        op, cnt = ops[0], len(ops)
        label = f"{op.name} x{cnt}"
        try:
            r = profile_op(op, "bfloat16", conv_layout=layout,
                           flash_attention=flash)
            fwd, bwd = r["fwd_ms"], r["bwd_ms"]
        except Exception as e:  # a compile error on one shape must
            # not lose the chip time already spent on earlier groups
            failed.append(label)
            print(f"[{i + 1}/{len(groups)}] {label:38s} "
                  f"{op.op_type.value:12s} FAILED ({type(e).__name__})",
                  flush=True)
            continue
        if fwd != fwd or bwd != bwd:  # NaN: unprofilable —
            # excluding (not zeroing) keeps the attribution honest
            failed.append(label)
            print(f"[{i + 1}/{len(groups)}] {label:38s} "
                  f"{op.op_type.value:12s} FAILED (NaN)", flush=True)
            continue
        tot = (fwd + bwd) * cnt
        by_type[op.op_type.value] += tot
        rows.append((tot, fwd, bwd, cnt, op.name, op.op_type.value))
        print(f"[{i + 1}/{len(groups)}] {label:38s} "
              f"{op.op_type.value:12s} fwd {fwd:7.3f}  bwd {bwd:7.3f}  "
              f"group {tot:8.2f} ms", flush=True)

    total = sum(by_type.values())
    if not total:
        raise SystemExit(
            f"no op group profiled successfully ({len(failed)} failed)")
    if failed:
        print(f"\nWARNING: {len(failed)} op groups failed to profile and "
              f"are EXCLUDED from the aggregate: {failed}")
    print(f"\n== per-type aggregate ({model_name}, b{batch} bf16, "
          f"layout={layout}, flash={flash}) ==")
    for k, v in sorted(by_type.items(), key=lambda kv: -kv[1]):
        print(f"{k:14s} {v:8.2f} ms  {100 * v / total:5.1f}%")
    print(f"{'SUM':14s} {total:8.2f} ms  (end-to-end bench: see bench.py"
          " row; sum excludes cross-op fusion)")

    print(f"\n== top {top} op groups ==")
    for tot, fwd, bwd, cnt, name, kind in sorted(rows, reverse=True)[:top]:
        print(f"{tot:8.3f} ms  {name:30s} x{cnt:3d} {kind:12s} "
              f"(fwd {fwd:.3f} / bwd {bwd:.3f} each)")


if __name__ == "__main__":
    main()
