#!/usr/bin/env python
"""Flash-attention tune-or-retire study (VERDICT round-2 ask #9).

Benchmarks jax's library Pallas TPU flash kernel (forward, in the (n,h,s,d)
layout it wants) against XLA's fused dense attention across sequence lengths
and kernel block sizes on the attached chip, with the repo's own kernel
(ops/flash_kernel.py, in the projections' (n,s,h*d) layout) as one more row;
the decision (ship which path at which lengths) is recorded in README.md.

Usage (chip must be free):  python scripts/tune_flash_attention.py
"""

import sys
import time

sys.path.insert(0, ".")

import jax

from flexflow_tpu.compile_cache import enable as _enable_cache
_enable_cache()
import jax.numpy as jnp
import numpy as np


def bench(fn, *args, iters=10):
    """Two-point slope timing: time N and 3N dispatches, each window
    ended by ``block_until_ready``, and take the slope — the constant
    (dispatch + wait) term cancels exactly.  Host jitter swamps sub-ms
    kernels, so scale N to a ~200ms window and take the median of 3."""
    fn_j = jax.jit(fn)
    jax.block_until_ready(fn_j(*args))
    jax.block_until_ready(fn_j(*args))

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn_j(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    def slope(n):
        t1 = run(n)
        t3 = run(3 * n)
        return max(0.0, (t3 - t1) / (2 * n))

    est = slope(iters)
    n = iters
    if est * n < 0.2:
        n = min(1000, int(0.2 / max(est, 2e-4)) + 1)
    return sorted(slope(n) for _ in range(3))[1] * 1e3


def main():
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)

    n, h, d = 8, 12, 64
    rng = np.random.default_rng(0)
    for s in (512, 1024, 2048, 4096):
        q = jnp.asarray(rng.standard_normal((n, h, s, d)),
                        jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((n, h, s, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((n, h, s, d)), jnp.bfloat16)
        scale = 1.0 / np.sqrt(d)

        def xla_dense(q, k, v):
            s_ = jnp.einsum("nhqd,nhkd->nhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(s_, axis=-1)
            return jnp.einsum("nhqk,nhkd->nhqd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)

        t_xla = bench(xla_dense, q, k, v)
        results = [("xla_fused", t_xla)]
        for bq, bkv in ((512, 512), (512, 1024), (1024, 512),
                        (256, 512), (1024, 1024)):
            if bq > s or bkv > s:
                continue
            bs = BlockSizes(
                block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
                block_q_major_dkv=bq, block_k_major_dkv=bkv,
                block_k_dkv=bkv, block_q_dkv=bq,
                block_k_major_dq=bkv, block_k_dq=bkv, block_q_dq=bq)
            try:
                t = bench(lambda q, k, v, bs=bs: flash_attention(
                    q, k, v, causal=False, sm_scale=scale, block_sizes=bs),
                    q, k, v)
                results.append((f"flash_q{bq}_kv{bkv}", t))
            except Exception as e:
                results.append((f"flash_q{bq}_kv{bkv}",
                                float("nan")))
                print(f"  s={s} q{bq}/kv{bkv}: {type(e).__name__}",
                      flush=True)
        try:
            t_def = bench(lambda q, k, v: flash_attention(
                q, k, v, causal=False, sm_scale=scale), q, k, v)
            results.append(("flash_default", t_def))
        except Exception:
            pass
        # the repo's own kernel (ops/flash_kernel.py) on the same values
        # in ITS layout, (n, s, h * d); its blocks are chosen in its module
        from flexflow_tpu.ops.flash_kernel import flash_attention as owned

        def fold(x):
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(n, s, h * d)

        results.append(("owned_kernel", bench(
            lambda q, k, v: owned(q, k, v, h, False, scale),
            fold(q), fold(k), fold(v))))
        best = min((t for _, t in results if np.isfinite(t)))
        print(f"s={s}:", flush=True)
        for name, t in sorted(results, key=lambda r: r[1]):
            mark = " <== best" if t == best else ""
            print(f"  {name:20s} {t:8.3f} ms{mark}", flush=True)


if __name__ == "__main__":
    main()
