#!/usr/bin/env python3
"""Times the dispatch of one sparse layer that holds 16 of 256 experts BOTH
ways on the chip, at the widths of
``perfbench/configs/pangu-ultra-moe-718b.json``:

    chiprun --chips 1 -- python3 scripts/moe_dispatch_forms.py

* ``whole``: everything after the sort once over all ``A = tokens x 8``
  pairs, the rows that are not this op's masked (``MoE._experts`` where an
  op holds every expert or a gradient is taken; for an op that holds 16 of
  256 it was the only form before PR 45);
* ``C=<rows>``: the op's OWN pairs only, ``C`` rows at a time under a loop
  (what ``MoE._experts`` does where an op holds fewer experts than its
  router scores), with ``C`` held to 256, 512 and 1 024 for a 512-token
  chunk's 4 096 pairs, and ``MoE.block_rows``' own answer beside them.

``MoE._experts`` alone is timed (sort, gather, the two grouped products,
combine; the router and the shared expert are the same either way), on
routings RIGGED so that 0, 256, 1 024 or all 4 096 of a chunk's pairs fall
on the held experts (a token step's 256 pairs: 0, 16, 256), and every
form's output is compared with ``whole``'s.  The tree keeps the rule that
wins (PERF.md section 5 has the table); this script is how to ask again.
"""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.op import OpContext
from flexflow_tpu.ops.moe import MoE
from flexflow_tpu.tensor import Tensor


class Whole(MoE):
    """The same op held to the one pass over all pairs: reached as a
    gradient reaches it, with the grouped product a serving step takes."""

    def _grouped_core(self, xs, w_up, w_dn, ctx):
        return super()._grouped_core(
            xs, w_up, w_dn, dataclasses.replace(ctx, training=False))


def blocks_of(rows):
    """The same op with its blocks held to ``rows`` rows."""
    return type(f"Blocks{rows}", (MoE,), {"block_rows": staticmethod(
        lambda tokens, k, held, experts: min(rows, tokens * k))})


def rigged(rng, tokens, k, experts, held, own):
    """``top_idx`` (tokens, k), distinct experts a token, of which ``own``
    pairs in all fall on experts ``0 .. held`` (spread evenly over the
    tokens, drawn evenly over the held experts) and the rest elsewhere."""
    each = np.full(tokens, own // tokens)
    each[:own % tokens] += 1
    rows = [np.concatenate([
        rng.choice(held, n, replace=False),
        held + rng.choice(experts - held, k - n, replace=False)])
        for n in each]
    return np.stack([rng.permutation(r) for r in rows]).astype(np.int32)


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "pangu-ultra-moe-718b.json")) as f:
        cfg = json.load(f)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind!r}",
          flush=True)
    small = dev.platform != "tpu"      # a CPU rehearsal of the script
    d = 256 if small else cfg["hidden_size"]
    ff = 128 if small else cfg["moe_intermediate_size"]
    held, experts = cfg["n_routed_experts"], cfg["published"][
        "n_routed_experts"]
    k = cfg["num_experts_per_tok"]
    dtype = jnp.dtype(cfg["run"]["compute_dtype"])
    rng = np.random.default_rng(45)
    key = jax.random.PRNGKey(45)
    weights = tuple(
        (0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)).astype(dtype)
        for i, shape in enumerate(((held, d, 2 * ff), (held, ff, d))))
    for tokens, owns in ((512, (0, 256, 1024, 4096)), (32, (0, 16, 256))):
        A = tokens * k
        x = Tensor(shape=(1, tokens, d), dtype="float32", name="x")
        rule = MoE.block_rows(tokens, k, held, experts)
        forms = [("whole", Whole, True)] + [
            (f"C={c}", blocks_of(c), False)
            for c in sorted({256, 512, 1024, rule}) if c <= A]
        xt = (0.5 * jax.random.normal(key, (tokens, d), jnp.float32)
              ).astype(dtype)
        gates = jnp.asarray(rng.random((tokens, k)), jnp.float32)
        table = {}
        for own in owns:
            top_idx = jnp.asarray(rigged(rng, tokens, k, experts, held, own))
            want = None
            for name, cls, training in forms:
                op = cls("moe", x, experts, ff, k=k, capacity_factor=None,
                         aux_loss_weight=0.0, gated=True, held=(0, held))
                ctx = OpContext(training=training, compute_dtype=str(dtype),
                                mesh=None)
                step = jax.jit(lambda w, xt, idx, g, op=op, ctx=ctx:
                               op._experts(w, xt, idx, g, tokens, 0, ctx,
                                           ("chunk", tokens)))
                out, ran = step(weights, xt, top_idx, gates)
                out.block_until_ready()
                ts = []
                for _ in range(10):
                    t0 = time.perf_counter()
                    step(weights, xt, top_idx, gates)[0].block_until_ready()
                    ts.append(1e3 * (time.perf_counter() - t0))
                got = np.asarray(out, np.float32)
                if want is None:
                    want = got
                else:
                    assert [int(v) for v in ran] == [
                        own, -(-own // op.dispatch["chunk", tokens]["rows"])]
                table[own, name] = float(np.median(ts))
                print(f"A={A} own={own} {name}"
                      f"{' (the rule)' if name == f'C={rule}' else ''}: "
                      f"median {np.median(ts):.3f} ms of 10 (min "
                      f"{min(ts):.3f}), core "
                      f"{op.grouped_product['chunk', tokens]}, largest "
                      f"difference from whole {np.abs(got - want).max():.4g}"
                      f" (largest output {np.abs(want).max():.4g})",
                      flush=True)
        print(f"A={A}: ms by own pairs x form " + json.dumps(
            {f"{own}": {name: round(table[own, name], 3)
                        for name, _, _ in forms} for own in owns}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
