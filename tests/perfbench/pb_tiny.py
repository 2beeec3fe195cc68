"""Test-only entry: a temporary copy of the benchmark with tiny cells added
as NEW files and NEW entries (no existing file edited), which the harness
finds by name and runs on the CPU with its look for a chip skipped."""

from __future__ import annotations

import json
import os
import shutil
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_ENC = {
    "name": "tiny-enc", "source": "test", "family": "postnorm_transformer",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 128, "vocab_size": 211,
    "max_position_embeddings": 32,
    "keys": {"layers": "num_hidden_layers", "d_model": "hidden_size",
             "heads": "num_attention_heads", "d_ff": "intermediate_size",
             "vocab": "vocab_size", "positions": "max_position_embeddings"},
    "reduced": [],
    "run": {"causal": False, "head": "classifier", "num_labels": 2,
            "layer_norm_eps": 1e-05, "hidden_act": "gelu_tanh",
            "compute_dtype": "bfloat16", "param_dtype": "float32"}}

TINY_LM = dict(TINY_ENC, name="tiny-lm", max_position_embeddings=64,
               vocab_size=2048,
               run={"causal": True, "head": "lm", "layer_norm_eps": 1e-05,
                    "hidden_act": "gelu_tanh", "compute_dtype": "bfloat16",
                    "param_dtype": "float32"})

TINY_TRAIN = {
    "kind": "train_steps", "program_args": ["-ll:tpu", "1", "-b", "32"],
    "global_batch": 32, "seq_len": 32, "host_batches": 4,
    "adam": {"alpha": 0.0001, "beta1": 0.9, "beta2": 0.999,
             "epsilon": 1e-08},
    "compared_steps": 3, "reference_micro_batch": 4, "warmup_steps": 1,
    "trace_after_s": 0.2, "trace_steps": 3}

TINY_SERVE = {
    "kind": "serve_closed", "program_args": ["-ll:tpu", "1", "-b", "2"],
    "program_args_traced": ["--trace-sample-rate", "1"],
    "slots": 4, "clients": 3,
    "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                   "min": 4, "max": 20},
    "new_tokens": {"dist": "uniform", "min": 16, "max": 40},
    "size_grid": 16, "grid_seed": 7,
    "warm_s": 0.3, "drain_s": 20.0, "trace_after_s": 0.2, "trace_s": 0.3,
    "compared_requests": 16}

# set from CPU readings at this size (PR 23), 26 sound seeds and 14 of the
# float8 control: grad_sample_rel_diff sound <= 0.00365, control >= 0.0109
# (the number the control has to fail); the others are three times the sound
# runs' largest, each held against a fault: loss_gap sound <= 0.00043 (half
# the batch left out reads 0.009-0.021), grad_norm_worst_leaf sound <= 0.0023
# (half the batch: 0.13), delta_norm_worst_leaf sound <= 0.0062 (a step that
# returns its state unchanged reads 1.0)
TRAIN_LIMITS = {"loss_gap": 0.0013, "grad_norm_worst_leaf": 0.007,
                "delta_norm_worst_leaf": 0.019, "grad_sample_rel_diff": 0.0063}
# twelve sound seeds and six of the control: served_gap_mean sound <= 0.000126,
# control >= 0.000328; served_gap_widest sound <= 0.0066 (an altered token
# reads 0.5 and more)
SERVE_LIMITS = {"served_gap_mean": 0.0002, "served_gap_widest": 0.02}


def tiny_tree(tmp_path):
    """Copy BENCHMARK.json and perfbench/ to ``tmp_path`` and ADD two tiny
    cells: two configurations, two traffic mixes, two workload files, one
    per-layer metric, and their entries."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _listing(root)
    home = os.path.join(root, "perfbench")

    def put(rel, doc):
        with open(os.path.join(home, rel), "x") as f:   # never overwrites
            json.dump(doc, f)

    put("configs/tiny-enc.json", TINY_ENC)
    put("configs/tiny-lm.json", TINY_LM)
    put("traffic/tiny-train.json", TINY_TRAIN)
    put("traffic/tiny-closed.json", TINY_SERVE)
    put("workloads/tiny-enc.train.json",
        {"why": "test", "limits": TRAIN_LIMITS})
    put("workloads/tiny-lm.serve.json",
        {"why": "test", "who": "test", "limits": SERVE_LIMITS})
    with open(os.path.join(home, "layer_metrics", "steps_counted.py"),
              "x") as f:
        f.write("def read(obs):\n"
                "    return float(obs.counters.get('tokens_per_step', 0))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] += [
        {"name": n, "source": "test", "file": f"perfbench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny-enc", "tiny-lm")]
    bench["workloads"] += [
        {"name": "tiny-enc.train", "config": "tiny-enc",
         "traffic": "tiny-train", "chips": 1, "why": "test"},
        {"name": "tiny-lm.serve", "config": "tiny-lm",
         "traffic": "tiny-closed", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kinds = {w.split(".")[1] for w in m["workloads"]}
            m["workloads"] += [n for n in ("tiny-enc.train", "tiny-lm.serve")
                               if n.split(".")[1] in kinds]
    bench["per_layer"].append(
        {"name": "steps_counted", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "L1 graph + compile",
         "moves": "train_tokens_per_s", "workloads": ["tiny-enc.train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _listing(root)
    changed = [p for p in before if p != "BENCHMARK.json"
               and before[p] != after.get(p)]
    assert not changed, f"files of the benchmark were edited: {changed}"
    return root


def _listing(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hash(fh.read())
    return out


def run(root, workload, seed=3, seconds=1.0, trace=0):
    """The rest of a run with the harness's look for a chip skipped."""
    from perfbench.harness import runner

    return runner.run_cell(root, workload, seed, seconds, trace,
                           t_start=time.perf_counter(), require_tpu=False)
