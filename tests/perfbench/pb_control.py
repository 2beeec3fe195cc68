#!/usr/bin/env python3
"""The control of "How correct is decided", at a cell's own size:

    python3 tests/perfbench/pb_control.py --workload <cell> --seeds 1 2 3

The reference is put in the program's place, computed with the operands of
every matrix product rounded through float8_e4m3fn (the nearest precision
below the bfloat16 the configurations state), and held to the cell's own
comparison and limits.  It has to come out as NOT correct on every seed.  The
benchmark's own runs never run this; the CPU tests run it at a small size.

For a training cell ``--sound-seeds 4 5 6 ...`` also reads the PROGRAM's
numbers on those seeds through one compiled model (what a run of the
benchmark compares, without its window), and ``--half-batch-seed 7`` reads
them once more with the second half of every batch's rows replaced by the
first: the readings a limit is set from, in one process on the chip.
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONTROL = "float8_e4m3fn"


def control_numbers(cell, seed):
    """``[(name, value, limit), ...]`` of the control for one seed."""
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    driver = cell.module("drivers", cell.traffic["kind"])
    sz, tr, lim = fam.sizes(cell.config), cell.traffic, cell.doc["limits"]
    if tr["kind"] == "train_steps":
        feed = driver.make_feed(tr, sz, seed)[:int(tr["compared_steps"])]
        micro = int(tr["reference_micro_batch"])
        want = ref.train_steps(sz, seed, feed, dict(tr["adam"]), micro)
        got = ref.train_steps(sz, seed, feed, dict(tr["adam"]), micro, CONTROL)
        index = fam.leaf_index(sz)
        as_program = {"losses": got["losses"]}
        for what in ("grad_norms", "delta_norms", "grad_samples"):
            as_program[what] = {
                n: np.asarray(got[what][k] if layer is None
                              else got[what][k][layer], np.float64)
                for n, (k, layer) in index.items()}
        return driver.compare(as_program, want, index, lim)
    # serving: at each position of seeded prompts and tokens, the gap of the
    # token the control puts first (it need not decode)
    sizes = driver.request_sizes(tr)[:int(tr["compared_requests"])]
    rng = np.random.default_rng([int(seed), 9])
    reqs = [(driver.prompt_tokens(sz["vocab"], seed, k, p),
             rng.integers(1, sz["vocab"], n)) for k, (p, n) in enumerate(sizes)]
    gaps = ref.served_gaps(sz, seed, reqs, CONTROL)
    widest, mean = driver.gap_numbers([g["control"] for g in gaps])
    return [("served_gap_mean", mean, lim["served_gap_mean"]),
            ("served_gap_widest", widest, lim["served_gap_widest"])]


def program_numbers(cell, seeds, half_batch_seed=None):
    """``{seed: [(name, value, limit), ...]}`` of the program itself on a
    training cell: one compiled model, each seed's weights and feed in turn.
    The key ``"half"`` holds ``half_batch_seed`` with part of the batch left
    out underneath."""
    from flexflow_tpu.model import FFModel

    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    driver = cell.module("drivers", cell.traffic["kind"])
    sz, tr = fam.sizes(cell.config), cell.traffic
    model = fam.build_train(cell.config, tr, {})
    index = fam.leaf_index(sz)
    inner = FFModel.train_batch

    def half(self, x, y):
        h = len(x) // 2
        return inner(self, np.concatenate([x[:h], x[:h]]),
                     np.concatenate([y[:h], y[:h]]))

    out = {}
    for key, seed in [(s, s) for s in seeds] + (
            [("half", half_batch_seed)] if half_batch_seed is not None else []):
        FFModel.train_batch = half if key == "half" else inner
        try:
            prog, feed = driver.seed_first_steps(cell, fam, ref, model, seed)
        finally:
            FFModel.train_batch = inner
        want = ref.train_steps(sz, seed, feed[:int(tr["compared_steps"])],
                               dict(tr["adam"]),
                               int(tr["reference_micro_batch"]))
        out[key] = driver.compare(prog, want, index, cell.doc["limits"]) + [
            ("grad_norm_over_mass", driver.cancellation(want, index), 1.0)]
    return out


def _line(what, cell, seed, numbers):
    return (f"{what} {cell.name} seed {seed}: " + ", ".join(
        f"{n} {v:.6g} (limit {limit:g})" for n, v, limit in numbers))


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sound-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--half-batch-seed", type=int)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    args = ap.parse_args(argv)
    from perfbench.harness import cells

    cell = cells.load(args.root, args.workload)
    if args.sound_seeds or args.half_batch_seed is not None:
        for seed, numbers in program_numbers(
                cell, args.sound_seeds, args.half_batch_seed).items():
            print(_line("program", cell, seed, numbers), flush=True)
    caught = 0
    for seed in args.seeds:
        numbers = control_numbers(cell, seed)
        bad = [n for n, v, limit in numbers if not v <= limit]
        caught += bool(bad)
        print(_line("control", cell, seed, numbers)
              + f" -> {'NOT correct' if bad else 'passes: the limits are too loose'}",
              flush=True)
    return 0 if caught == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
