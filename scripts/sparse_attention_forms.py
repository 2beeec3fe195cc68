#!/usr/bin/env python3
"""Times the forms of attention with a learned selection on the chip, at the
widths of ``perfbench/configs/keye-vl-2.0-30b-a3b.json``:

    chiprun --chips 1 -- python3 scripts/sparse_attention_forms.py

One layer's whole step (projections, indexer, cache writes, scores, choice,
core, output) against a 25 088-position table, pages of 16:

* a 512-token CHUNK whose last row stands at ``L`` = 4 096, 12 288 and
  24 576, the threshold of a row's 2 048 best found two ways: ``sorted``
  (``jax.lax.top_k``, which the TPU's compiler lowers to a full sort of
  every row) and ``bits`` (the 2 048-th largest found bit by bit on the
  scores' order-preserving integer image: 32 counting passes over the
  ``(512, 25 088)`` buffer and, only where scores tie AT the threshold, 15
  more for the last tied position chosen).  Both are exact and give the same
  set; the tree keeps one as ``ops/attention.select_threshold`` and the
  other is written HERE only;
* a TOKEN step of 24 slots at positions 8 191-24 575, the chosen set
  reaching the core two ways: ``rows`` (the 2 048 chosen rows a slot copied
  out of the pools, then dense attention over them: what the op does on a
  TPU) and ``gathered`` (each slot's whole page table written out as a view
  and masked: what it does elsewhere).

The tree keeps the forms that win (PERF.md section 5 has the numbers); this
script is how to ask again.
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
from flexflow_tpu.ops import attention as att
from flexflow_tpu.tensor import Tensor


def threshold_sorted(scores, topk):
    """:func:`flexflow_tpu.ops.attention.select_threshold`'s contract by
    ``jax.lax.top_k``: of equal scores it puts the lower position first."""
    vals, idx = jax.lax.top_k(scores, topk)
    thr = vals[..., -1]
    last = jnp.max(jnp.where(vals == thr[..., None], idx, -1), axis=-1)
    return thr, last


class Gathered(att.MultiHeadAttention):
    """The same op held to the masked view in its token step."""

    def _decode_core(self, pool, ctx):
        return "gathered"


def _timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(ts)), out


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        cfg = json.load(f)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind!r}",
          flush=True)
    small = dev.platform != "tpu"      # a CPU rehearsal of the script
    sa = cfg["sa_config"]
    d = 128 if small else cfg["hidden_size"]
    heads, kv_heads = (4, 2) if small else (cfg["num_attention_heads"],
                                            cfg["num_key_value_heads"])
    hd = 16 if small else cfg["head_dim"]
    topk = 64 if small else sa["topk"]
    chunk, page = (64, 16) if small else (512, 16)
    max_seq = 1024 if small else cfg["run"]["max_seq"]
    slots = 4 if small else 24
    pps = max_seq // page
    pages = slots * pps
    ctx = OpContext(training=False, compute_dtype="bfloat16", mesh=None)
    key = jax.random.PRNGKey(0)

    def make(cls, n, w):
        x = Tensor(shape=(n, w, d), dtype="float32", name="x")
        op = cls("attention_0", x, x, x, d, heads, causal=True,
                 use_bias=False, num_kv_heads=kv_heads, head_dim=hd,
                 rope={"rope_theta": cfg["rope_theta"]},
                 qk_norm=cfg["rms_norm_eps"],
                 sparse={"index_heads": sa["indexer_num_heads"],
                         "index_dim": sa["indexer_head_dim"], "topk": topk})
        params = {p.name: (jnp.ones(p.shape, jnp.bfloat16)
                           if p.name.endswith("norm") else
                           (0.02 * jax.random.normal(
                               jax.random.fold_in(key, i), p.shape,
                               jnp.float32)).astype(jnp.bfloat16))
                  for i, p in enumerate(op.weights)}
        return op, params

    def pools(op):
        def pool(i, width):
            return (0.5 * jax.random.normal(
                jax.random.fold_in(key, 100 + i), (pages, page, width),
                jnp.float32)).astype(jnp.bfloat16)
        ik = pool(2, op.index_width).at[..., op.index_dim:].set(0)
        return {"k": pool(0, op.kv_dim), "v": pool(1, op.kv_dim), "ik": ik}

    results = {}
    # ---- the chunk, its threshold two ways -------------------------------
    op, params = make(att.MultiHeadAttention, 1, chunk)
    state = pools(op)
    table = jnp.arange(pps, dtype=jnp.int32)
    rows = (0.5 * jax.random.normal(key, (1, chunk, d), jnp.float32)
            ).astype(jnp.bfloat16)
    kept = att.select_threshold
    for name, fn in (("bits", kept), ("sorted", threshold_sorted)):
        att.select_threshold = fn

        @jax.jit
        def step(params, rows, state, start):
            out, new = op.serve_step(params, [rows], state, ServeStep(
                "chunk", table, start=start, length=jnp.int32(chunk),
                slot=jnp.int32(0), no_page=pages), ctx)
            return out[0]

        for L in ((256, 1024) if small else (4096, 12288, 24576)):
            ms, out = _timed(step, params, rows, state, jnp.int32(L - chunk))
            results[name, L] = (ms, np.asarray(out, np.float32))
            print(f"chunk {name:8s} L={L:6d}: {ms:8.3f} ms "
                  f"({op.chunk_core})", flush=True)
    att.select_threshold = kept
    for L in ((256, 1024) if small else (4096, 12288, 24576)):
        a, b = results["bits", L][1], results["sorted", L][1]
        print(f"chunk L={L}: largest difference between the two "
              f"{float(np.abs(a - b).max()):.3g}", flush=True)
    # ---- the token step, the chosen set two ways --------------------------
    pos = jnp.asarray(np.linspace(max_seq // 3, max_seq - chunk - 1,
                                  slots).astype(np.int32))
    tab = jnp.arange(pages, dtype=jnp.int32).reshape(slots, pps)
    wp = jnp.take_along_axis(tab, (pos // page)[:, None], axis=1)[:, 0]
    tok = (0.5 * jax.random.normal(key, (slots, 1, d), jnp.float32)
           ).astype(jnp.bfloat16)
    outs = {}
    for name, cls in (("as the op chooses", att.MultiHeadAttention),
                      ("gathered", Gathered)):
        op, params = make(cls, slots, 1)
        state = pools(op)

        @jax.jit
        def step(params, tok, state):
            out, new = op.serve_step(params, [tok], state, ServeStep(
                "token", tab, pos=pos, write_pages=wp, write_rows=pos % page,
                no_page=pages), ctx)
            return out[0]

        ms, out = _timed(step, params, tok, state)
        outs[name] = np.asarray(out, np.float32)
        print(f"token {name:24s} ({op.decode_core}): {ms:8.3f} ms",
              flush=True)
    a, b = outs.values()
    print(f"token: largest difference between the two "
          f"{float(np.abs(a - b).max()):.3g} (outputs up to "
          f"{float(np.abs(a).max()):.3g})", flush=True)


if __name__ == "__main__":
    main()
