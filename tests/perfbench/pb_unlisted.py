#!/usr/bin/env python3
"""Chip-side entry: one TRACED run of a cell with the per-layer metrics of
``data/unlisted_metrics.json`` read beside the listed ones.

    python3 tests/perfbench/pb_unlisted.py --workload <cell> --seed <n> \
        --seconds <s> [--save-trace <name> [--save-seconds <s>]]
    python3 tests/perfbench/pb_unlisted.py ... --profiler 0

The readers are under ``perfbench/layer_metrics/``; BENCHMARK.json does not
list them yet (the data file says why), so the benchmark's own command never
calls them.  This is the harness's ``run_cell`` with those entries appended
to the cell's list, nothing else changed; the last line is the result object.
``--save-trace`` also writes the reduced trace's first ``--save-seconds`` of
the traced window and the numbers a CPU test recomputes from it to
``chiprun_out/<name>.json`` and ``<name>.answers.json``.

``--profiler 0`` measures what the program's own tracing costs: an UNTRACED
run (end-to-end metrics, no profiler session) of a program started with the
mix's ``program_args_traced`` (``--trace-sample-rate 1``), to set beside the
benchmark's own untraced runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)


def unlisted():
    with open(os.path.join(HERE, "data", "unlisted_metrics.json")) as f:
        return json.load(f)["per_layer"]


def with_unlisted(load, spans_only=False):
    """``cells.load`` with the unlisted entries of the cell appended; with
    ``spans_only`` the program gets its traced arguments in every run."""
    def loader(root, workload):
        cell = load(root, workload)
        cell.per_layer = cell.per_layer + [
            m for m in unlisted() if workload in m["workloads"]]
        if spans_only:
            tr = cell.traffic
            cell.traffic = dict(tr, program_args=list(tr["program_args"])
                                + list(tr.get("program_args_traced", [])))
        return cell
    return loader


def serve_answers(xtrace, doc, program):
    """What ``test_perfbench_tracing`` recomputes from a saved serve trace."""
    from perfbench.harness.stats import median

    win = xtrace.window(doc)
    busy_s, window_s, gaps = xtrace.busy(doc, win, [min(doc["devices"])])
    return {"decode_program": program,
            "decode_device_ms": median(
                xtrace.module_times_ms(doc, program + "(")),
            "device_idle_share.serve": 100.0 * (1.0 - busy_s / window_s),
            "idle_gaps": xtrace.idle_gaps(doc, gaps, n=20)}


def main(argv):
    from perfbench.harness import cells, runner, xtrace

    ap = argparse.ArgumentParser(prog="pb_unlisted.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save-trace", default="")
    ap.add_argument("--save-seconds", type=float, default=1.0)
    ap.add_argument("--profiler", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    docs = []
    load_trace = xtrace.load

    def keeping(path):
        docs.append(load_trace(path))
        return docs[-1]

    cells.load = with_unlisted(cells.load, spans_only=not args.profiler)
    xtrace.load = keeping
    result = runner.run_cell(REPO, args.workload, args.seed, args.seconds,
                             args.profiler, T_START)
    if args.save_trace:
        from flexflow_tpu.obs.trace import get_tracer

        out = os.path.join(REPO, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        path = os.path.join(out, args.save_trace + ".json")
        first = xtrace.window(docs[-1])[0]
        xtrace.save(docs[-1], path, first,
                    first + int(args.save_seconds * 1e9))
        programs = [s["args"]["program"]
                    for s in get_tracer().snapshot()["spans"]
                    if s["name"] == "decode_step"]
        with open(os.path.join(out, args.save_trace + ".answers.json"),
                  "w") as f:
            json.dump(serve_answers(xtrace, load_trace(path), programs[0]),
                      f, indent=1)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
