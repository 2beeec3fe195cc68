#!/usr/bin/env python3
"""Chip-side entry: sets of untraced runs of one cell, one process a run, and
each set's spread as the driver's check reads it.

    python3 tests/perfbench/pb_sets.py --workload <cell> --name <file> \\
        --sets 30x6@3200001000 51x6@3200002000 30x6@3200001000

A set is ``<seconds>x<runs>@<first seed>`` (seeds consecutive); the sets run
in the order given, so two window lengths can share one machine's drift.
This process never touches jax: each run is the benchmark's own command.
Every run's metrics and the serve driver's sub-window lines are appended to
``chiprun_out/<file>.json`` as they come, so a call that is cut keeps what
it had; ``--deadline-s`` starts no run after that many seconds.  A
``model_config`` PR that adds a serve cell reports its own two sets of six
with this (PERF.md section 2).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from perfbench.harness.stats import (  # noqa: E402 — after the path
    spread_less_farthest, spread_quartiles)

SUBWINDOW = re.compile(r"sub-window ([\d.]+)-([\d.]+) s: (.*?) \(")


def parse_subwindow(line):
    """One of the serve driver's sub-window lines as a dict, or None."""
    m = SUBWINDOW.search(line)
    if not m:
        return None
    numbers = (kv.split(" ") for kv in m.group(3).split(", "))
    return {"from_s": float(m.group(1)), "to_s": float(m.group(2)),
            **{k: float(v) for k, v in numbers}}


def parse_sets(words):
    out = []
    for w in words:
        m = re.fullmatch(r"(\d+(?:\.\d+)?)x(\d+)@(\d+)", w)
        if not m:
            raise SystemExit(f"pb_sets: not <seconds>x<runs>@<seed>: {w!r}")
        out.append({"seconds": float(m.group(1)),
                    "seeds": [int(m.group(3)) + i
                              for i in range(int(m.group(2)))], "runs": []})
    return out


def one_run(command, workload, seed, seconds):
    t0 = time.perf_counter()
    p = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed), "--seconds",
                   f"{seconds:g}", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True)
    run = {"seed": seed, "rc": p.returncode,
           "wall_s": time.perf_counter() - t0}
    lines = p.stdout.strip().splitlines()
    if p.returncode or not lines:
        run["stderr"] = p.stderr[-2000:]
        return run
    last = json.loads(lines[-1])
    run.update(correct=last["correct"], attempted=last["attempted"],
               failed=last["failed"], compared=last["compared"],
               memory_peak_bytes=last["device"]["memory_peak_bytes"],
               metrics={k: v["value"] for k, v in last["metrics"].items()})
    subs = [sub for sub in map(parse_subwindow, lines) if sub]
    if subs:
        run["subwindows"] = subs
    return run


def summary(sets):
    lines = []
    for i, s in enumerate(sets):
        good = [r for r in s["runs"] if "metrics" in r]
        if len(good) < 3:
            continue
        for name in good[0]["metrics"]:
            vs = [r["metrics"][name] for r in good]
            lines.append(
                f"set {i + 1} ({s['seconds']:g} s, {len(good)} runs) {name}: "
                f"median {statistics.median(vs)!r}, spread less the farthest "
                f"{spread_less_farthest(vs):.5f}, quartiles "
                f"{spread_quartiles(vs):.5f}, runs {vs!r}")
    return lines


def main(argv):
    ap = argparse.ArgumentParser(prog="pb_sets.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--name", required=True)
    ap.add_argument("--sets", nargs="+", required=True)
    ap.add_argument("--deadline-s", type=float, default=float("inf"))
    args = ap.parse_args(argv)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    sets = parse_sets(args.sets)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    t_start = time.perf_counter()
    for i, s in enumerate(sets):
        for seed in s["seeds"]:
            if time.perf_counter() - t_start > args.deadline_s:
                print(f"pb_sets: deadline, set {i + 1} seed {seed} not run",
                      flush=True)
                continue
            run = one_run(command, args.workload, seed, s["seconds"])
            s["runs"].append(run)
            print(f"set {i + 1} seed {seed} +{time.perf_counter() - t_start:.0f}"
                  f" s: " + json.dumps({k: run.get(k) for k in
                                        ("rc", "correct", "failed",
                                         "metrics")}), flush=True)
            with open(os.path.join(out, args.name + ".json"), "w") as f:
                json.dump({"workload": args.workload, "sets": sets}, f,
                          indent=1)
    for line in summary(sets):
        print(line, flush=True)
    bad = [r for s in sets for r in s["runs"]
           if r["rc"] or not r.get("correct") or r.get("failed")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
