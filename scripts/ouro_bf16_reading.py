#!/usr/bin/env python3
"""What bfloat16 ALONE does to the tokens of a stack run four times, no
program involved:

    python3 scripts/ouro_bf16_reading.py [--seeds 1 2] [--workload <cell>]

The ouro cell's plain reference (``perfbench/reference/ouro.py``) computed
twice over the cell's first 16 request sizes, in float32 and in the
arithmetic the configuration COMPUTES in (``"bfloat16"``: the operands of
every product and what every sublayer hands on rounded through bfloat16),
and held to the cell's own comparison: how far the token the bfloat16
arithmetic puts first lies below the float32 reference's best, on average
and at the widest.  It is ``pb_control.py``'s comparison with bfloat16 in
the control's place, and it is no control: it has to come out CORRECT by the
cell's limits, and its reading is the floor of what a sound program that
computes in bfloat16 reads (PERF.md section 2).  On the chip at the cell's
size about a minute a seed; on the CPU use a tiny preset's tree.
"""

import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def reading(cell, seed, rounding="bfloat16"):
    """``(mean, widest)`` of the gap of the token ``rounding`` arithmetic
    puts first, over the cell's compared request sizes."""
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    driver = cell.module("drivers", cell.traffic["kind"])
    sz, tr = fam.sizes(cell.config), cell.traffic
    sizes = driver.request_sizes(tr)[:int(tr["compared_requests"])]
    rng = np.random.default_rng([int(seed), 9])
    reqs = [(driver.prompt_tokens(sz["vocab"], seed, k, p),
             rng.integers(1, sz["vocab"], n))
            for k, (p, n) in enumerate(sizes)]
    gaps = ref.served_gaps(sz, seed, reqs, rounding)
    widest, mean = driver.gap_numbers([g["control"] for g in gaps])
    return mean, widest


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ouro-2.6b.serve.closed-12-reason")
    ap.add_argument("--seeds", type=int, nargs="*", default=[1])
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    from perfbench.harness import cells

    cell = cells.load(args.root, args.workload)
    lim = cell.doc["limits"]
    for seed in args.seeds:
        mean, widest = reading(cell, seed)
        print(f"bfloat16 alone, {cell.name} seed {seed}: served_gap_mean "
              f"{mean:.6g} (limit {lim['served_gap_mean']:g}), "
              f"served_gap_widest {widest:.6g} (limit "
              f"{lim['served_gap_widest']:g})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
