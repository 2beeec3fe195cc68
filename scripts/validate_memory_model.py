#!/usr/bin/env python
"""Validate the search cost model's HBM high-water estimate against the
TPU compiler's own accounting (VERDICT r4 ask #6).

``jit(...).lower().compile().memory_analysis()`` on the TPU backend
reports the real buffer-assignment peak; the CPU test backend's numbers
do not model thunk liveness (see tests/test_remat_memory.py), so this
comparison runs on the bench chip.  For each config (model x remat) it
prints analytic ``Simulator.peak_memory_bytes`` vs the compiler's
``temp + argument`` bytes and their ratio.  Compile-only: nothing
executes, so one run fits a short chip window.

Run on the bench chip:   python scripts/validate_memory_model.py
Results recorded in BASELINE.md ("Memory-model validation").
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import bench
from flexflow_tpu.config import ParallelConfig
from flexflow_tpu.compile_cache import enable as _enable_cache  # noqa: E402
_enable_cache()
from flexflow_tpu.search.simulator import Simulator


def main():
    bench._require_tpu()  # XLA:TPU's buffer assignment is the subject
    import jax

    rows = []
    for model_name, batch in [("alexnet", 128), ("inception_v3", 64)]:
        for remat in (False, True):
            model, xs, y = bench.build(model_name, batch)
            model.config.remat = remat
            model._build_step_fns()  # rebuild with the remat flag
            batch_sh = model._shard_batch(tuple(xs) + (y,))
            comp = model._train_step.lower(
                model._params, model._opt_state, batch_sh, 0).compile()
            ma = comp.memory_analysis()
            xla = ma.temp_size_in_bytes + ma.argument_size_in_bytes
            sim = Simulator(num_devices=1, remat=remat, opt_slot_bytes=0)
            serial = {op.name: ParallelConfig.data_parallel(
                1, op.outputs[0].num_dims) for op in model.layers}
            ours = sim.peak_memory_bytes(model.layers, serial)
            row = {"model": model_name, "remat": remat,
                   "batch": batch,
                   "xla_temp_mb": round(ma.temp_size_in_bytes / 1e6, 1),
                   "xla_args_mb": round(
                       ma.argument_size_in_bytes / 1e6, 1),
                   "xla_total_mb": round(xla / 1e6, 1),
                   "analytic_mb": round(ours / 1e6, 1),
                   "ratio": round(ours / xla, 3)}
            rows.append(row)
            print(json.dumps(row), flush=True)
            del model, comp
    ratios = [r["ratio"] for r in rows]
    print(json.dumps({"metric": "memval_summary", "n": len(rows),
                      "min_ratio": min(ratios),
                      "max_ratio": max(ratios)}), flush=True)


if __name__ == "__main__":
    main()
