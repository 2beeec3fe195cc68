#!/usr/bin/env python3
"""Times a prompt chunk's latent attention THREE ways on the chip, at the
widths of ``perfbench/configs/pangu-ultra-moe-718b.json``:

    chiprun --chips 1 -- python3 scripts/latent_chunk_forms.py

* ``expanded``: the slot's cached rows expanded to per-head keys and values
  a block at a time under XLA's loop (``LatentAttention._over_key_blocks``):
  what ``serve_step("chunk")`` does wherever the kernel is not taken;
* ``absorbed``: the form the token step takes, applied to the chunk's 512
  queries (``W_UK`` moved to the queries, scores and values over the latent
  rows as they lie, ``W_UV`` after), written HERE only, over the same blocks
  under the same loop;
* ``kernel``: the expanded form inside the repo's own
  ``ops/latent_chunk_kernel.py`` (a block's keys, values and scores stay in
  VMEM): what ``serve_step("chunk")`` does on a TPU, the op as it is.

One layer's whole chunk step (projections, cache write, core, output) is
timed for a 512-token chunk whose last row stands at ``L`` = 2 048 and
12 288, the three differing in the core alone.  The tree keeps the form
that wins (PERF.md section 5 has the numbers); this script is how to ask
again.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.op import OpContext, ServeStep
from flexflow_tpu.ops.latent_attention import LatentAttention
from flexflow_tpu.tensor import Tensor


class ExpandedLoop(LatentAttention):
    """The same op held to the loop over key blocks."""

    def _chunk_core(self, q, pool, ctx):
        return "loop"


class AbsorbedChunk(ExpandedLoop):
    """The same op with the chunk's core absorbed."""

    def _chunk_expanded(self, params, q_nope, q_pe, pool, where, ctx):
        q = self._absorbed_queries(params, q_nope, q_pe, ctx)[0]  # (B, H, e)

        def block(rows):
            s = jnp.einsum("qhe,ke->hqk", q, rows,
                           preferred_element_type=jnp.float32)
            return s, lambda p: jnp.einsum(
                "hqk,kc->hqc", p.astype(rows.dtype), rows[:, :self.kv_rank],
                preferred_element_type=jnp.float32)

        u = self._over_key_blocks(pool, where, q.shape[0], self.kv_rank,
                                  block)
        return self._values_out(params, u, ctx)


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
    heads = 4 if small else cfg["num_attention_heads"]
    chunk, page, max_seq = 512, 16, 12800
    x = Tensor(shape=(1, chunk, d), dtype="float32", name="x")
    kw = dict(q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
              nope_dim=cfg["qk_nope_head_dim"],
              rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
              rope_theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"])
    ctx = OpContext(training=False, compute_dtype="bfloat16", mesh=None)
    pps = max_seq // page
    key = jax.random.PRNGKey(0)
    results = {}
    for name, cls in (("expanded", ExpandedLoop),
                      ("absorbed", AbsorbedChunk),
                      ("kernel", LatentAttention)):
        if small and name == "kernel":
            continue    # the CPU's answer is the loop again
        op = cls("attention_0", x, heads, **kw)
        params = {w.name: (jnp.ones(w.shape, jnp.bfloat16)
                           if w.name.endswith("norm") else
                           (0.02 * jax.random.normal(
                               jax.random.fold_in(key, i), w.shape,
                               jnp.float32)).astype(jnp.bfloat16))
                  for i, w in enumerate(op.weights)}
        pool = (0.5 * jax.random.normal(key, (pps + 8, page, op.row_width),
                                        jnp.float32)).astype(jnp.bfloat16)
        pool = pool.at[..., op.row_values:].set(0)
        table = jnp.arange(pps, dtype=jnp.int32)
        rows = (0.5 * jax.random.normal(key, (1, chunk, d), jnp.float32)
                ).astype(jnp.bfloat16)

        @jax.jit
        def step(params, rows, pool, start):
            out, state = op.serve_step(params, [rows], {"kv": pool}, ServeStep(
                "chunk", table, start=start, length=jnp.int32(chunk),
                slot=jnp.int32(0), no_page=pps + 8), ctx)
            return out[0], state["kv"]

        for L in (2048, 12288):
            start = jnp.int32(L - chunk)
            out, _ = step(params, rows, pool, start)
            out.block_until_ready()
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                out, _ = step(params, rows, pool, start)
                out.block_until_ready()
                ts.append(1e3 * (time.perf_counter() - t0))
            results[name, L] = (float(np.median(ts)), np.asarray(
                out, np.float32))
            print(f"{name} L={L}: median {np.median(ts):.3f} ms of 5 "
                  f"(min {min(ts):.3f}) a layer's chunk step", flush=True)
        if name == "kernel":
            assert op.chunk_core == {chunk: "kernel"}, op.chunk_core
    for L in (2048, 12288):
        a = results["expanded", L][1]
        for other in ("absorbed", "kernel"):
            if (other, L) not in results:
                continue
            b = results[other, L][1]
            print(f"L={L}: largest difference of expanded and {other} "
                  f"{np.abs(a - b).max():.4g} (largest output "
                  f"{np.abs(a).max():.4g}); expanded/{other} "
                  f"{results['expanded', L][0] / results[other, L][0]:.3f}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
