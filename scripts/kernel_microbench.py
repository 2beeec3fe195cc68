"""Isolated on-chip A/B of the round-5 kernel lowerings.

Times each alternative lowering against XLA's stock path on the exact
Inception-stem shapes the round-5 attribution charged: max-pool
backward (SelectAndScatter vs the equality-mask VJP), stride-2 conv
dgrad (dilated-grad conv vs the parity-phase decomposition), and the
NHWC channel concat boundary.
A full-model bench folds the input pipeline and every other op into one
number; this isolates the kernels, completes inside ~2 min of chip time,
and prints one JSON line per pair.  Each timed window of dispatches is
ended by ``block_until_ready``; min over repeats.  One chip command
(ROADMAP S4).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

B = int(os.environ.get("FF_MB_BATCH", "128"))
ITERS = int(os.environ.get("FF_MB_ITERS", "30"))
REPEATS = int(os.environ.get("FF_MB_REPEATS", "3"))

import jax

from flexflow_tpu.compile_cache import enable as _enable_cache
_enable_cache()

import jax.numpy as jnp
from jax import lax


def timed(fn, *args, iters=None, repeats=None):
    """min-over-repeats seconds per execution.  Dispatches ``iters``
    copies (they serialize on the device stream) and waits once on the
    last output; min over repeats rejects host hiccups."""
    iters = iters or ITERS
    repeats = repeats or REPEATS
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))  # compile

    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / iters


def row(name, stock_s, fast_s):
    print(json.dumps({
        "metric": f"microbench_{name}", "value": round(stock_s / fast_s, 3),
        "unit": "stock/fast speedup", "vs_baseline": None,
        "stock_ms": round(stock_s * 1e3, 3),
        "fast_ms": round(fast_s * 1e3, 3)}), flush=True)


def pool_pair():
    """Stem max-pool 3x3 s2 bwd: b128 NHWC 147x147x64 (bf16).
    Stock = reduce_window + SelectAndScatter."""
    from flexflow_tpu.ops.conv import _fast_max_pool

    x = jnp.ones((B, 147, 147, 64), jnp.bfloat16)

    def stock(v):
        return jax.grad(lambda u: jnp.sum(
            lax.reduce_window(u, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "VALID").astype(jnp.float32)))(v)

    def fast(v):
        return jax.grad(lambda u: jnp.sum(_fast_max_pool(
            u, (3, 3), (2, 2), (0, 0), (1, 2)).astype(jnp.float32)))(v)

    row("pool_bwd_stem", timed(stock, x), timed(fast, x))


def dgrad_pair():
    """Stem conv 3x3 s2 dgrad: b128 NHWC 149x149x32 <- 147x147x32."""
    from flexflow_tpu.ops.conv import _conv_dn, _phase_dgrad

    dy = jnp.ones((B, 74, 74, 32), jnp.bfloat16)
    w = jnp.ones((3, 3, 32, 32), jnp.bfloat16)
    xshape = (B, 149, 149, 32)

    def stock(g):
        # XLA's dgrad formulation: conv of the interior-dilated grad
        # with the spatially-flipped, io-swapped filter
        return lax.conv_general_dilated(
            g, jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2)),
            window_strides=(1, 1), padding=[(2, 2), (2, 2)],
            lhs_dilation=(2, 2), dimension_numbers=_conv_dn(True))

    def fast(g):
        return _phase_dgrad(g, w, xshape, (2, 2), (0, 0), True)

    row("dgrad_s2_stem", timed(stock, dy), timed(fast, dy))


def pallas_norm_pair():
    """Transformer residual+LayerNorm: fused single-pass Pallas kernel
    (ops/pallas_norm.py) vs the stock add + f32-stats norm — the shape
    class the pipeline block's two ln(x + attn) sites run (b x s x d).
    Decides the `pallas_norm` tuned-table flag (default OFF until this
    measures a win on the device kind)."""
    from flexflow_tpu.ops.pallas_norm import (fused_layernorm,
                                              _ln_reference, supported)

    x = jnp.ones((B, 128, 512), jnp.bfloat16)
    r = jnp.ones((B, 128, 512), jnp.bfloat16)
    s = jnp.ones((512,), jnp.float32)
    b = jnp.ones((512,), jnp.float32)
    if not supported(x.shape, x.dtype):
        print(json.dumps({"metric": "microbench_pallas_norm_res",
                          "value": None, "unit": "stock/fast speedup",
                          "vs_baseline": None,
                          "error": "shape not supported"}), flush=True)
        return

    def stock(v, w):
        return _ln_reference(v, w, s, b, 1e-5)

    def fast(v, w):
        return fused_layernorm(v, w, s, b, 1e-5)

    try:
        row("pallas_norm_res", timed(stock, x, r), timed(fast, x, r))
    except Exception as e:  # Mosaic lowering failures stay local
        print(json.dumps({"metric": "microbench_pallas_norm_res",
                          "value": None, "unit": "stock/fast speedup",
                          "vs_baseline": None,
                          "error": f"{type(e).__name__}: {e}"[:300]}),
              flush=True)


def concat_pair():
    """Channel concat between NHWC-internal convs: stock = concat in
    NCHW (boundary transposes), fast = lane-axis concat."""
    xs = [jnp.ones((B, 64, 35, 35), jnp.bfloat16) for _ in range(4)]

    def stock(*vs):
        return jnp.concatenate(vs, axis=1)

    def fast(*vs):
        t = [jnp.transpose(v, (0, 2, 3, 1)) for v in vs]
        return jnp.transpose(jnp.concatenate(t, axis=3), (0, 3, 1, 2))

    row("concat_lane", timed(stock, *xs), timed(fast, *xs))


def main():
    dev = jax.devices()[0]
    print(json.dumps({"metric": "microbench_device",
                      "value": 1, "unit": str(dev.device_kind),
                      "vs_baseline": None}), flush=True)
    pool_pair()
    pallas_norm_pair()
    dgrad_pair()
    concat_pair()
    print("microbench models_ok", flush=True)


if __name__ == "__main__":
    main()
