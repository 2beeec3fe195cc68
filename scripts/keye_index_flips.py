#!/usr/bin/env python3
"""What bfloat16 arithmetic ALONE does to the Keye-VL reference's learned
selection: how often a query's set of chosen keys differs from the float32
one, by how many keys, and whether those queries carry the gaps the
benchmark's comparison reads.

    python3 scripts/keye_index_flips.py [--config perfbench/configs/keye-vl-2.0-30b-a3b.json]
        [--seed N] [--sequences 2] [--length 8192]

No program of the repo is involved: the plain reference
(``perfbench/reference/keye_vl.py``) runs twice over the same seeded random
token sequences, once exact and once with both operands of every matrix
product rounded through bfloat16 (the precision the configuration computes
in; the indexer's products among them).  At every layer the two passes'
chosen sets are compared query by query, over the queries past ``topk``
(before it every live key is chosen in both); at the end, for every position,
how far the token the bfloat16 pass puts first lies below the float32 pass's
best logit: the number ``served_gap_*`` reads of a served stream.  Printed:
the share of (query, layer) pairs whose set differs and the mean number of
keys such a pair swaps (of ``topk``), and the gap's mean, 99th percentile and
maximum over all positions, over those whose set differs in some layer and
over the others.  A count, not a timing (the chip only makes it quick); one
JSON object is the last line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join(
        REPO, "perfbench", "configs", "keye-vl-2.0-30b-a3b.json"))
    ap.add_argument("--seed", type=int, default=4400044901)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--length", type=int, default=8192)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from perfbench.families import keye_vl as fam
    from perfbench.reference import keye_vl as ref

    with open(args.config) as f:
        sz = fam.sizes(json.load(f))
    params = ref.init_params(sz, args.seed)
    rng = np.random.default_rng([args.seed, 9])
    seqs = [rng.integers(1, sz["vocab"], args.length).astype(np.int32)
            for _ in range(args.sequences)]
    arith = ("float32", "bfloat16")
    sets = jax.jit(lambda x, p, a: ref.chosen_sets(
        x, p, sz, ref.ROUNDINGS[a]), static_argnums=2)
    emb = params.leaf("tok_emb")
    xs = {a: [jnp.take(emb, jnp.asarray(t), axis=0) for t in seqs]
          for a in arith}
    del emb
    past = np.arange(args.length) >= sz["topk"]
    differs, swapped = [], []       # per layer: (queries past topk,)
    for layer in range(len(sz["layers"])):
        p = params.layer(layer)
        attn = {k: p[k] for k in ref.ATTENTION}
        swaps = []
        for i in range(args.sequences):
            a, b = (sets(xs[name][i], attn, name) for name in arith)
            # keys in the float32 set that the bfloat16 one left out
            swaps.append(np.asarray(jnp.sum(a & ~b, axis=-1))[past])
        swaps = np.concatenate(swaps)
        differs.append(swaps > 0)
        swapped.append(swaps)
        print(f"layer {layer}: {differs[-1].mean():.4%} of queries past "
              f"topk choose another set in bfloat16, "
              f"{swaps[swaps > 0].mean() if swaps.any() else 0.0:.2f} keys "
              f"of {sz['topk']} swapped where they do", flush=True)
        for name in arith:
            xs[name] = ref.layer_forward(xs[name], p, sz, layer, name)
        del p
    g = params.leaf("g_final")
    head = params.leaf("head")
    gaps = []
    for t, x, xl in zip(seqs, xs["float32"], xs["bfloat16"]):
        best, _, ctrl = ref._gap_rows(
            ref.rms_norm(x, g, sz["eps"]), ref.rms_norm(xl, g, sz["eps"]),
            head, jnp.asarray(t), "bfloat16")
        gaps.append((np.asarray(best, np.float64)
                     - np.asarray(ctrl, np.float64))[past])
    gap = np.concatenate(gaps)
    any_flip = np.any(np.stack(differs), axis=0)

    def numbers(v):
        return {"positions": int(v.size), "mean": float(v.mean()),
                "p99": float(np.quantile(v, 0.99)), "widest": float(v.max()),
                "share_not_the_best": float((v > 0).mean())} if v.size else {}

    every = np.concatenate(swapped)
    out = {"seed": args.seed, "sequences": args.sequences,
           "length": args.length, "platform": jax.devices()[0].platform,
           "topk": sz["topk"],
           "pairs_with_another_set": float(np.mean(np.stack(differs))),
           "keys_swapped_where_a_set_differs":
           float(every[every > 0].mean()) if every.any() else 0.0,
           "keys_swapped_most": int(every.max()),
           "queries_with_another_set_somewhere": float(any_flip.mean()),
           "by_layer": [float(d.mean()) for d in differs],
           "gap_all": numbers(gap), "gap_where_a_set_differs":
           numbers(gap[any_flip]), "gap_where_none_does":
           numbers(gap[~any_flip])}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
