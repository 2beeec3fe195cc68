#!/usr/bin/env python3
"""What bfloat16 arithmetic ALONE does to the Laguna reference: how often a
token's set of chosen experts differs from the float32 one, and whether
those tokens carry the gaps the benchmark's comparison reads.

    python3 scripts/laguna_expert_flips.py [--config perfbench/configs/laguna-xs2.json]
        [--seed N] [--sequences 4] [--length 1024]

No program of the repo is involved: the plain reference
(``perfbench/reference/laguna.py``) runs twice over the same seeded random
token sequences, once exact and once with both operands of every matrix
product rounded through bfloat16 (the precision the configuration computes
in).  At every sparse layer the two routings are compared token by token
(top-k as a SET); at the end, for every position, how far the token the
bfloat16 pass puts first lies below the float32 pass's best logit: the
number ``served_gap_*`` reads of a served stream.  Printed: the share of
(token, sparse layer) pairs and of tokens whose set differs, and the gap's
mean, 99th percentile and maximum over all tokens, over the tokens with a
differing set somewhere and over the others.  Runs on the CPU (a count, not
a timing); one JSON object is the last line.
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
        REPO, "perfbench", "configs", "laguna-xs2.json"))
    ap.add_argument("--seed", type=int, default=3600036901)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--length", type=int, default=1024)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from perfbench.families import laguna as fam
    from perfbench.reference import laguna as ref

    with open(args.config) as f:
        sz = fam.sizes(json.load(f))
    params = ref.init_params(sz, args.seed)
    rng = np.random.default_rng([args.seed, 9])
    seqs = [rng.integers(1, sz["vocab"], args.length).astype(np.int32)
            for _ in range(args.sequences)]
    arith = ("float32", "bfloat16")

    def chosen(x, g2, wr, arithmetic):
        b = ref.rms_norm(x, g2, sz["eps"])
        return jnp.sort(ref.route(b, wr, sz, ref.ROUNDINGS[arithmetic])[0],
                        axis=-1)

    chosen = jax.jit(chosen, static_argnums=3)
    emb = params.leaf("tok_emb")
    xs = {a: [jnp.take(emb, jnp.asarray(t), axis=0) for t in seqs]
          for a in arith}
    del emb
    differs = []                    # per sparse layer: (tokens,) bool
    for layer, spec in enumerate(sz["layers"]):
        p = params.layer(layer)
        if spec["mlp"] == "sparse":
            # the routing reads the layer's input AFTER its attention: run
            # the attention alone first (a dense layer 0 shape stands in)
            attn_only = dict(sz, layers=[dict(l, mlp="dense")
                                         for l in sz["layers"]])
            zero = {k: jnp.zeros(ref.leaf_shape(sz, k, layer), jnp.float32)
                    for k in ref.DENSE}
            sets = {}
            for a in arith:
                mid = ref.layer_forward(xs[a], {**p, **zero}, attn_only,
                                        layer, a)
                sets[a] = chosen(jnp.concatenate(mid, axis=0), p["g2"],
                                 p["wr"], a)
            differs.append(np.asarray(jnp.any(
                sets["float32"] != sets["bfloat16"], axis=-1)))
            print(f"layer {layer}: {differs[-1].mean():.4%} of tokens route "
                  f"to another set of experts in bfloat16", flush=True)
        for a in arith:
            xs[a] = ref.layer_forward(xs[a], p, sz, layer, a)
        del p
    g = params.leaf("g_final")
    head = params.leaf("head")
    gaps = []
    for t, x, xl in zip(seqs, xs["float32"], xs["bfloat16"]):
        best, _, ctrl = ref._gap_rows(
            ref.rms_norm(x, g, sz["eps"]), ref.rms_norm(xl, g, sz["eps"]),
            head, jnp.asarray(t), "bfloat16")
        gaps.append(np.asarray(best, np.float64) - np.asarray(ctrl,
                                                              np.float64))
    gap = np.concatenate(gaps)
    any_flip = np.any(np.stack(differs), axis=0)

    def numbers(v):
        return {"tokens": int(v.size), "mean": float(v.mean()),
                "p99": float(np.quantile(v, 0.99)), "widest": float(v.max()),
                "share_not_the_best": float((v > 0).mean())} if v.size else {}

    out = {"seed": args.seed, "sequences": args.sequences,
           "length": args.length, "platform": jax.devices()[0].platform,
           "pairs_with_another_set": float(np.mean(np.stack(differs))),
           "tokens_with_another_set_somewhere": float(any_flip.mean()),
           "by_layer": [float(d.mean()) for d in differs],
           "gap_all": numbers(gap), "gap_where_a_set_differs":
           numbers(gap[any_flip]), "gap_where_none_does":
           numbers(gap[~any_flip]),
           "of_the_20_widest_gaps_with_a_differing_set":
           int(any_flip[np.argsort(gap)[-20:]].sum())}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
