"""MultiHeadAttention + sequence-parallel ring attention.

The reference has **no attention ops** (SURVEY §5 "no attention ops exist");
this is the new workload BASELINE.json config 5 adds.  Design is TPU-first:

* the dense path is one fused chain of einsums (QKV projection → scores →
  softmax → context → output projection) that XLA maps onto the MXU, with
  float32 softmax statistics;
* the sequence-parallel path is **ring attention**: query blocks stay
  resident on their shard of the ``s`` mesh axis while key/value blocks
  rotate around the ring via ``lax.ppermute``, combined with an online
  (flash-style) softmax so the full score matrix never materializes.  This
  is the long-context scaling story the reference lacks entirely — its only
  sequence partitioning is NMT timestep *pipelining* (nmt/rnn.h:23).

Gradients for the ring path come from jax autodiff through the
``shard_map``-ed scan (ppermute is linear; its transpose is the reverse
rotation), so there is no hand-written backward.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..initializers import (ConstantInitializer, GlorotUniform,
                            ZeroInitializer)
from ..op import Op, OpContext, OpType
from .common import add_wide, cast_compute, read_wide
from .norm import rms_normalize

NEG_INF = -1e30  # finite mask value: keeps online-softmax exp() NaN-free
_LANES = 128
_KEY_BLOCK = 512    # keys a block of a sparse op's long chunk history


def _use_flash(q, k, ctx_flag, training_dropout: bool,
               training: bool = True) -> bool:
    """Flash or dense.  ``ctx_flag`` None = auto: flash at s >= 512
    when training, s >= 1024 forward-only.  Two measured v5e crossovers
    feed the split threshold (BASELINE.md "Flash attention"), BOTH taken
    with jax's library kernel behind its layout transposes, before the
    repo had a kernel of its own (ROADMAP S5 re-derives them against
    :mod:`flash_kernel`): forward-only, dense wins at s=512 (1.17x) and
    flash at s >= 1024 (2.7-2.8x) — so inference keeps 1024.  The
    round-5 TRAINING A/B (bench.py --flash on|off, BERT-base s=512)
    flipped the s=512 verdict for the full step: the dense path's
    O(s^2) f32 score matrix in backward costs more than the library
    kernel's forward handicap (107.25 ms vs 109.09 ms per step, 43.9% vs
    43.2% MFU), so training uses 512 (with the owned kernel the same A/B
    reads 65.51 ms vs 108.56 ms — BASELINE.md).  Flash is the only option at
    s >= 8192 where the dense score matrix exceeds HBM.  Either kernel
    requires TPU, 128-aligned seq lens, lane-block head_dim, and no
    attention-prob dropout (it never materializes probabilities);
    :func:`_flash_core` then says WHICH kernel."""
    if training_dropout or jax.default_backend() != "tpu":
        return False
    sq, sk, d = q.shape[1], k.shape[1], q.shape[3]
    ok = (sq % 128 == 0 and sk % 128 == 0
          and (d < 128 or d % 128 == 0)
          and q.dtype in (jnp.float32, jnp.bfloat16))
    if ctx_flag is None:
        return ok and max(sq, sk) >= (512 if training else 1024)
    return ctx_flag and ok


def _tuned_block_sizes(sq: int, sk: int):
    """Blocks of jax's LIBRARY kernel only (the owned kernel chooses its
    own, in :mod:`flash_kernel`), v5e-tuned
    (scripts/tune_flash_attention.py): q 512 / kv 1024 is within 4% of
    best at every measured s >= 1024.  Falls back to kernel defaults
    when the tuned blocks don't divide the seq lens."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    bq = 512 if sq % 512 == 0 else None
    bkv = next((b for b in (1024, 512) if sk % b == 0), None)
    if bq is None or bkv is None:
        return None
    return BlockSizes(
        block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bkv,
        block_k_dkv=bkv, block_q_dkv=bq,
        block_k_major_dq=bkv, block_k_dq=bkv, block_q_dq=bq)


def _shard_axes(q, mesh):
    """The mesh axes the batch and the heads of ``q`` (n, s, h, d) split
    over when the flash kernel runs per shard (``None`` = the dim stays
    whole: one chip, or an axis that does not divide it), and the heads a
    shard then holds."""
    if mesh is None or not mesh.is_distributed:
        return None, None, q.shape[2]

    def axes(axis, size):
        return (mesh.subaxes(axis) or None
                if size % mesh.axis_size(axis) == 0 else None)

    c = axes("c", q.shape[2])
    return (axes("n", q.shape[0]), c,
            q.shape[2] // (mesh.axis_size("c") if c else 1))


def _flash_core(q, k, mesh=None) -> str:
    """Which flash kernel the operands get, from what a shard sees:
    ``"owned"`` (:mod:`flash_kernel`: head size 64 with an even number of
    local heads, or a lane multiple) or ``"library"`` (jax's kernel behind
    layout transposes, e.g. three local heads when 12 split four ways)."""
    from . import flash_kernel

    heads = _shard_axes(q, mesh)[2]
    return ("owned" if flash_kernel.supported(heads, q.shape[3], q.shape[1],
                                              k.shape[1]) else "library")


def _flash_attention(q, k, v, causal: bool, scale: float, mesh=None):
    """Pallas TPU flash attention: blockwise online softmax on-chip — the
    VMEM-resident fused kernel the pallas_guide prescribes for the
    attention hot op.  :func:`_flash_core` picks the kernel.  The OWNED
    one reads ``(n, s, h * d)``, the projections' own layout: the fold
    here cancels against ``_qkv``'s unfold (and ``_out_proj`` folds
    first thing), so no layout op is left round it.  The LIBRARY one
    (jax.experimental.pallas.ops.tpu) wants (n,h,s,d) and gets it through
    four transposes.

    On a distributed ``mesh`` the kernel runs per shard under shard_map:
    GSPMD treats a pallas_call as an opaque custom call and would
    all-gather its operands, so every chip would run the whole batch.
    Attention is independent per sample and per head, so the batch
    shards over ``n`` and the heads over ``c`` halo-free (a dim the
    axis does not divide stays whole); other mesh axes see replicas."""
    n_axes, c_axes, heads = _shard_axes(q, mesh)
    if _flash_core(q, k, mesh) == "owned":
        from .flash_kernel import flash_attention as _own

        def kern(q, k, v):
            return _own(q, k, v, heads, causal, scale)

        args = [x.reshape(x.shape[:2] + (-1,)) for x in (q, k, v)]
        spec = PartitionSpec(n_axes, None, c_axes)
    else:
        from jax.experimental.pallas.ops.tpu.flash_attention import \
            flash_attention as _fa

        blocks = _tuned_block_sizes(q.shape[1], k.shape[1])

        def kern(q, k, v):
            qt = jnp.transpose(q, (0, 2, 1, 3))
            kt = jnp.transpose(k, (0, 2, 1, 3))
            vt = jnp.transpose(v, (0, 2, 1, 3))
            out = _fa(qt, kt, vt, causal=causal, sm_scale=scale,
                      block_sizes=blocks)
            return jnp.transpose(out, (0, 2, 1, 3))

        args = [q, k, v]
        spec = PartitionSpec(n_axes, None, c_axes, None)
    if mesh is not None and mesh.is_distributed:
        kern = jax.shard_map(kern, mesh=mesh.mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)
    return kern(*args).reshape(q.shape)


def _qk(q, k):
    """``q k^T`` in f32, ``(n, h, q, k)``: ``q`` (n, q, h, d) against ``k``
    (n, k, g, d).  With as many key heads as query heads this is the one
    einsum it always was; with fewer (grouped queries) query head ``i``
    reads key head ``i // (h / g)``, the key never repeated in memory."""
    h, g = q.shape[2], k.shape[2]
    if h == g:
        return jnp.einsum("nqhd,nkhd->nhqk", q, k,
                          preferred_element_type=jnp.float32)
    n, sq, _, d = q.shape
    s = jnp.einsum("nqgrd,nkgd->ngrqk", q.reshape(n, sq, g, h // g, d), k,
                   preferred_element_type=jnp.float32)
    return s.reshape(n, h, sq, k.shape[1])


def _pv(probs, v):
    """``probs v`` in f32, ``(n, q, h, d)``: the twin of :func:`_qk`."""
    h, g = probs.shape[1], v.shape[2]
    if h == g:
        return jnp.einsum("nhqk,nkhd->nqhd", probs, v,
                          preferred_element_type=jnp.float32)
    n, _, sq, sk = probs.shape
    o = jnp.einsum("ngrqk,nkgd->nqgrd",
                   probs.reshape(n, g, h // g, sq, sk), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(n, sq, h, v.shape[3])


def rope_inv_freq(rope, head_dim: int):
    """``(inverse frequencies (rot / 2,), factor on cos and sin, rot)`` of
    a rotary embedding given as the published ``rope_parameters`` entry of
    one layer kind: ``rope_type`` ``"default"`` (plain, ``rope_theta``) or
    ``"yarn"`` (Peng et al. 2023 as ``transformers`` computes it: below
    ``beta_slow`` rotations over ``original_max_position_embeddings`` a
    dimension is interpolated by ``factor``, above ``beta_fast`` left as
    it is, a linear ramp between; cos and sin times ``attention_factor``),
    over the first ``partial_rotary_factor`` of the head."""
    import numpy as np
    rot = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0, rot
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: 'default' or 'yarn'")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return rot * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    att = rope.get("attention_factor")
    att = 0.1 * math.log(factor) + 1.0 if att is None else float(att)
    return inv.astype(np.float32), att, rot


def apply_rope(x, positions, rope):
    """Rotate the first ``rot`` dims of every head of ``x`` (n, s, h, d) to
    ``positions`` (n, s) or (s,), half-split pairing ``(i, i + rot / 2)``;
    the other dims pass.  Computed in f32, returned in ``x``'s dtype."""
    inv, att, rot = rope_inv_freq(rope, x.shape[-1])
    pos = jnp.asarray(positions, jnp.float32)
    if pos.ndim == 1:
        pos = pos[None]
    ang = pos[..., None] * jnp.asarray(inv)                   # (n, s, rot/2)
    cos = (jnp.cos(ang) * att)[:, :, None, :]
    sin = (jnp.sin(ang) * att)[:, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :rot // 2], xf[..., rot // 2:rot]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                           xf[..., rot:]], axis=-1)
    return out.astype(x.dtype)


def _window_mask(scores, kpos, qpos, window: int):
    """Also mask what a window leaves out: keys at ``kpos <= qpos -
    window``, and ring rows nobody wrote yet (``kpos < 0``).  ``kpos`` and
    ``qpos`` broadcast against ``scores`` (n, h, q, k)."""
    if not window:
        return scores
    return jnp.where((kpos <= qpos - window) | (kpos < 0), NEG_INF, scores)


def _keep_mask(scores, keep):
    """Also mask what a learned selection left out: ``keep`` broadcasts
    against ``scores`` (n, h, q, k); ``None`` (an op that chooses nothing,
    or a history no longer than its ``topk``) is the scores themselves, so
    the traced program is the one it always was."""
    if keep is None:
        return scores
    return jnp.where(keep, scores, NEG_INF)


def index_scores(qi, ki, wi):
    """The indexer's score of every (query, key) pair, f32 ``(n, q, k)``:
    ``sum_j wi[q, j] * relu(qi[q, j] . ki[k])`` over the index heads ``j``
    — ``qi`` (n, q, Hi, di), ``ki`` (n, k, di) (ONE key head), ``wi`` (n, q,
    Hi) f32.  The weighted sum is an elementwise product and a sum in f32,
    not a second matrix product (which a TPU would round to bfloat16)."""
    s = jnp.einsum("nqhd,nkd->nqhk", qi, ki,
                   preferred_element_type=jnp.float32)
    # (+ 0.0: a sum of products by negative weights can be -0.0, which
    # equals +0.0 and which a sort would put under it; one zero only)
    return jnp.sum(jax.nn.relu(s) * wi[..., None], axis=2) + 0.0


def select_threshold(scores, topk: int):
    """``(thr, last)`` of each row of ``scores`` (.., L) f32, ``L > topk``:
    the ``topk``-th largest score and the highest position among the chosen
    that hold exactly it — of equal scores the lower position is chosen
    first, so the chosen set is every position over ``thr`` and those AT it
    up to ``last`` (:func:`selected`): exact, ties and all.  Positions a row
    may not see carry ``NEG_INF`` and are chosen last.

    No sort (``jax.lax.top_k`` of 2 048 is, to the TPU's compiler, a full
    sort of every row): ``thr`` is found BIT BY BIT on the scores'
    order-preserving unsigned image, 32 passes that each count a row's
    scores at or over a candidate; ``last`` is ``L`` (every tied position is
    chosen) unless some row has more scores AT its threshold than it still
    needs, and only then a second search, over positions, finds where that
    row's need is met."""
    L = scores.shape[-1]
    # (-0.0 + 0.0 is +0.0: one image for the two zeros, which compare equal)
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)
    top = jnp.uint32(1 << 31)
    u = jnp.where(bits >= top, ~bits, bits | top)

    def count(mask):
        return jnp.sum(mask, axis=-1, dtype=jnp.int32)

    def bit(i, ans):
        cand = ans | (top >> i.astype(jnp.uint32))
        return jnp.where(count(u >= cand[..., None]) >= topk, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1],
                                                  jnp.uint32))
    thr = jax.lax.bitcast_convert_type(
        jnp.where(ans >= top, ans ^ top, ~ans), jnp.float32)
    tied = u == ans[..., None]
    need = topk - count(u > ans[..., None])            # >= 1 of the tied
    kpos = jnp.arange(L)
    width = L.bit_length()

    def where_need_is_met():
        # the largest P with fewer than ``need`` tied positions under it
        def step(i, p):
            cand = p | (1 << (width - 1 - i))
            return jnp.where(count(tied & (kpos < cand[..., None])) < need,
                             cand, p)
        return jax.lax.fori_loop(0, width, step, jnp.zeros_like(need))

    last = jax.lax.cond(jnp.all(count(tied) == need),
                        lambda: jnp.full_like(need, L), where_need_is_met)
    return thr, last


def selected(scores, kpos, thr, last):
    """The chosen set as a mask over ``scores`` (.., q, k): ``kpos``
    broadcasts against it, ``thr`` / ``last`` (.., q) are
    :func:`select_threshold`'s."""
    thr, last = thr[..., None], last[..., None]
    return (scores > thr) | ((scores == thr) & (kpos <= last))


def _decode_attention(q, k_cache, v_cache, pos, scale: float,
                      kpos=None, window: int = 0, keep=None):
    """Single-position attention against a preallocated per-slot KV
    cache (the autoregressive decode kernel — docs/serving.md "Token
    generation").  ``q``: (n, 1, h, d) — each slot's current-token
    query; ``k_cache``/``v_cache``: (n, max_seq, h, d); ``pos``: (n,)
    int32 position of the current token (whose K/V the caller already
    wrote).  Mirrors :func:`_dense_attention`'s arithmetic exactly —
    f32 scores, the same finite ``NEG_INF`` mask whose exp underflows
    to an exact 0.0 — so a decode step is bit-identical on CPU to the
    full-sequence forward's row at ``pos`` (tests/test_generation.py
    pins it at every prefix length).

    The single query is duplicated to TWO rows and row 0 kept: a
    ``(1, S) @ (S, d)`` probs x values product lowers to a
    matrix-VECTOR kernel whose accumulation order drifts ~1 ulp from
    the matrix-matrix path the full forward takes (measured on CPU;
    the same reason serving's shape buckets start at 2 — see
    serving/batcher.derive_buckets), while q >= 2 rows hit the
    identical gemm micro-kernel.  One duplicated query row is noise in
    a decode step."""
    q2 = jnp.concatenate([q, q], axis=1)                      # (n,2,h,d)
    scores = _qk(q2, k_cache) * scale
    if kpos is None:
        kpos = jnp.arange(k_cache.shape[1])[None, None, None, :]
    else:       # (n, L): the position each row of a ring view holds
        kpos = kpos[:, None, None, :]
    qpos = pos[:, None, None, None]
    scores = jnp.where(kpos > qpos, NEG_INF, scores)
    scores = _window_mask(scores, kpos, qpos, window)
    scores = _keep_mask(scores, keep)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _pv(probs.astype(v_cache.dtype), v_cache)
    return out[:, :1]


def _paged_chunk_attention(q, kg, vg, qpos, scale: float, kpos=None,
                           window: int = 0, keep=None):
    """Chunked-prefill attention against the gathered page view (the
    paged prefill kernel — docs/serving.md "Paged KV & prefix
    caching").  ``q``: (1, B, h, d) — the chunk's queries at GLOBAL
    positions ``qpos`` (B,); ``kg``/``vg``: (1, L, h, d) — the slot's
    page table gathered back into position order (history pages + the
    chunk's own rows, which the caller scattered in before gathering).
    Mirrors :func:`_dense_attention`'s causal arithmetic exactly — f32
    scores, the same finite ``NEG_INF`` mask whose exp underflows to an
    exact 0.0 — with the mask keyed on global positions, so a chunk's
    row t reproduces the monolithic forward's row t bit-identically on
    CPU (tests/test_generation.py pins it per chunk size).  Columns
    beyond a row's position (unwritten pool rows, stale page contents)
    contribute exact zeros, never values.  ``kpos`` (L,) and ``window``:
    the ring view of a windowed layer, as in :func:`_decode_attention`."""
    scores = _qk(q, kg) * scale
    if kpos is None:
        kpos = jnp.arange(kg.shape[1])
    kpos = kpos[None, None, None, :]
    qpos = qpos[None, None, :, None]
    scores = jnp.where(kpos > qpos, NEG_INF, scores)
    scores = _window_mask(scores, kpos, qpos, window)
    scores = _keep_mask(scores, keep)
    probs = jax.nn.softmax(scores, axis=-1)
    return _pv(probs.astype(vg.dtype), vg)


def _verify_window_attention(q, kg, vg, qpos, scale: float, keep=None):
    """Speculative-verify attention: a W-position window PER SLOT
    against each slot's gathered page view (docs/serving.md
    "Speculative decoding & sampling").  ``q``: (n, W, h, d) — slot i's
    queries at GLOBAL positions ``qpos[i] .. qpos[i]+W-1``;
    ``kg``/``vg``: (n, L, h, d) — each slot's page table gathered back
    into position order; ``qpos``: (n, W) int32 global positions.

    This is :func:`_paged_chunk_attention` batched over slots — the
    identical einsum/mask/softmax arithmetic with the causal mask keyed
    on per-slot global positions, so window row t is bit-identical on
    CPU to the sequential decode step at that position given the same
    cache content (the greedy-speculation parity pin's kernel half).
    Columns beyond a row's position — including the window's own
    not-yet-verified later rows and any stale speculated rows from a
    rolled-back round — contribute exact zeros, never values; rollback
    is free because visibility is the mask, not the write."""
    scores = _qk(q, kg) * scale
    kpos = jnp.arange(kg.shape[1])
    scores = jnp.where(kpos[None, None, None, :]
                       > qpos[:, None, :, None], NEG_INF, scores)
    scores = _keep_mask(scores, keep)
    probs = jax.nn.softmax(scores, axis=-1)
    return _pv(probs.astype(vg.dtype), vg)


def _dense_attention(q, k, v, causal: bool, scale: float,
                     dropout_rate: float, rng, window: int = 0, keep=None):
    """(n,sq,h,d),(n,sk,g,d),(n,sk,g,d) -> (n,sq,h,d); f32 softmax; with
    ``window`` a query at ``i`` sees keys ``i - window < j <= i`` only; with
    ``keep`` (n, 1, sq, sk) only the keys it marks (:func:`_keep_mask`)."""
    scores = _qk(q, k) * scale
    if causal:
        sq, sk = scores.shape[2], scores.shape[3]
        qpos = jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        scores = jnp.where(kpos > qpos, NEG_INF, scores)
        scores = _window_mask(scores, kpos, qpos, window)
    scores = _keep_mask(scores, keep)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and rng is not None:
        keep = 1.0 - dropout_rate
        mask = jax.random.bernoulli(rng, keep, probs.shape)
        probs = jnp.where(mask, probs / keep, 0.0)
    return _pv(probs.astype(v.dtype), v)


def _ring_attention_local(q, k, v, rng, *, s_axes, ring_size: int,
                          s_local: int, causal: bool, scale: float,
                          dropout_rate: float = 0.0):
    """Per-shard ring attention body (runs inside shard_map).

    q,k,v: (n, s_local, h, d) — this device's sequence block.  KV blocks
    rotate around the ring; an online softmax (running max ``m``, running
    denominator ``l``, unnormalized accumulator ``o``) merges each block's
    contribution, so peak memory is O(s_local^2) scores per step instead of
    O(s_local * s_global).
    """
    idx = jax.lax.axis_index(s_axes)
    n, sq, h, d = q.shape
    qf = q.astype(jnp.float32)
    m0 = jnp.full((n, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, h, sq), jnp.float32)
    o0 = jnp.zeros((n, sq, h, d), jnp.float32)
    perm = [(j, (j - 1) % ring_size) for j in range(ring_size)]
    qpos = idx * s_local + jnp.arange(sq)

    def body(carry, step):
        kb, vb, m, l, o = carry
        src = (idx + step) % ring_size  # owner of the block we now hold
        scores = jnp.einsum("nqhd,nkhd->nhqk", qf, kb.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = src * s_local + jnp.arange(kb.shape[1])
            scores = jnp.where(kpos[None, None, None, :]
                               > qpos[None, None, :, None], NEG_INF, scores)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        # the denominator accumulates the UNdropped p, so masking p only in
        # the numerator is exactly dense attention's dropout-after-softmax
        # (dropout commutes with the 1/l normalization)
        l_new = l * corr + p.sum(axis=-1)
        pv = p
        if dropout_rate > 0.0 and rng is not None:
            key = jax.random.fold_in(jax.random.fold_in(rng, idx), step)
            keep = 1.0 - dropout_rate
            mask = jax.random.bernoulli(key, keep, p.shape)
            pv = jnp.where(mask, p / keep, 0.0)
        o_new = (o * jnp.transpose(corr, (0, 2, 1))[..., None]
                 + jnp.einsum("nhqk,nkhd->nqhd", pv, vb.astype(jnp.float32),
                              preferred_element_type=jnp.float32))
        kb = jax.lax.ppermute(kb, s_axes, perm)
        vb = jax.lax.ppermute(vb, s_axes, perm)
        return (kb, vb, m_new, l_new, o_new), None

    (_, _, _, l, o), _ = jax.lax.scan(
        body, (k, v, m0, l0, o0), jnp.arange(ring_size))
    return o / jnp.transpose(l, (0, 2, 1))[..., None]


def ring_attention(q, k, v, mesh, causal: bool, scale: float,
                   dropout_rate: float = 0.0, rng=None):
    """Sequence-parallel attention over the mesh's ``s`` axis.

    q,k,v: (n, s, h, d) global arrays (sequence-sharded by GSPMD); the
    shard_map runs one ring per (n-shard, s-ring) with heads replicated.
    """
    s_axes = mesh.subaxes("s")
    n_axes = mesh.subaxes("n")
    ring_size = mesh.axis_size("s")
    s_local = q.shape[1] // ring_size
    n_sharded = bool(n_axes) and q.shape[0] % mesh.axis_size("n") == 0
    spec = PartitionSpec(n_axes if n_sharded else None, s_axes, None, None)
    fn = partial(_ring_attention_local, s_axes=s_axes, ring_size=ring_size,
                 s_local=s_local, causal=causal, scale=scale,
                 dropout_rate=dropout_rate if rng is not None else 0.0)
    if rng is None:
        wrapped = lambda q, k, v: fn(q, k, v, None)  # noqa: E731
        return jax.shard_map(wrapped, mesh=mesh.mesh,
                             in_specs=(spec, spec, spec), out_specs=spec,
                             check_vma=False)(q, k, v)
    return jax.shard_map(fn, mesh=mesh.mesh,
                         in_specs=(spec, spec, spec, PartitionSpec()),
                         out_specs=spec, check_vma=False)(q, k, v, rng)


class MultiHeadAttention(Op):
    """Reference-parity builder signature (the later FlexFlow generations
    expose ``multihead_attention(query, key, value, embed_dim, num_heads,
    ...)``); this snapshot has none, so the surface follows that convention.

    Weights follow Linear's (out, in) layout: wq/wk/wv project the model dim
    to ``num_heads*head_dim`` and are sharded over their out-dim on the
    ``c`` (tensor-parallel) mesh axis — Megatron-style head parallelism;
    wo projects back and shards over its *in* dim.
    """

    op_type = OpType.ATTENTION

    def __init__(self, name, query, key, value, embed_dim, num_heads,
                 kdim=0, vdim=0, dropout=0.0, use_bias=True, causal=False,
                 kernel_initializer=None, num_kv_heads=None, head_dim=None,
                 rope=None, gate=False, window=0, qk_norm=None, sparse=None):
        """Beyond the defaults (as many key/value heads as query heads,
        ``head_dim = embed_dim / num_heads``, learned positions elsewhere):
        ``num_kv_heads`` key/value heads shared by groups of ``num_heads /
        num_kv_heads`` query heads; a ``head_dim`` of its own (the
        projections are then ``embed_dim -> heads * head_dim`` and back);
        ``rope``, one layer kind's published ``rope_parameters`` entry
        (:func:`rope_inv_freq`), applied to q and k at their positions
        before the cores and before the cache's scatter; ``gate``, a
        per-head sigmoid gate ``sigmoid(x Wg)_h`` on the attention output;
        ``window``, causal attention over the last ``window`` positions
        only (and a cache that holds no more: :meth:`serve_state`);
        ``qk_norm``, the eps of an RMSNorm over ``head_dim`` on every query
        and key head between the projection and the rotation (one learned
        scale each, shared by the heads); ``sparse``, ``{"index_heads",
        "index_dim", "topk"}`` (and ``"eps"``, of the LayerNorm on the
        indexer's key, 1e-6 unless given): a learned INDEXER scores every cached
        position for each query (:func:`index_scores`) and the heads attend
        over the ``topk`` best only (all of them while the history is
        shorter), with a third cache leaf for the indexer's one key head
        (:meth:`_serve_step_sparse`)."""
        inputs = [query] if key is query and value is query else [
            query, key, value]
        super().__init__(name, inputs)
        self.embed_dim, self.num_heads = embed_dim, num_heads
        # kdim/vdim follow torch.nn.MultiheadAttention: the feature dims of
        # the key/value inputs — they must match the actual tensors
        self.kdim = kdim or key.shape[-1]
        self.vdim = vdim or value.shape[-1]
        assert self.kdim == key.shape[-1], (self.kdim, key.shape)
        assert self.vdim == value.shape[-1], (self.vdim, value.shape)
        if head_dim is None:
            assert embed_dim % num_heads == 0, (embed_dim, num_heads)
        self.head_dim = int(head_dim or embed_dim // num_heads)
        self.num_kv_heads = int(num_kv_heads or num_heads)
        assert num_heads % self.num_kv_heads == 0, (num_heads, num_kv_heads)
        self.q_dim = num_heads * self.head_dim
        self.kv_dim = self.num_kv_heads * self.head_dim
        self.rope = dict(rope) if rope else None
        self.gate, self.window = bool(gate), int(window or 0)
        assert not self.window or causal, "a window needs causal attention"
        self.dropout, self.causal, self.use_bias = float(dropout), causal, use_bias
        self._self_attn = len(inputs) == 1
        # what the flash kernels and the ring take: one head count, no
        # window, positions from elsewhere (ROADMAP M1/M2: their training
        # forms of grouped heads and of the window are not written)
        self.qk_norm = None if qk_norm is None else float(qk_norm)
        self.sparse = dict(sparse) if sparse else None
        self._plain = (self.num_kv_heads == num_heads and not self.window
                       and self.rope is None and not self.gate
                       and self.qk_norm is None and self.sparse is None)
        # {training: core} as last traced (see _attend)
        self.kernel_cores = {}
        # "paged" or "gathered": the decode core serve_step("token") got,
        # as last traced
        self.decode_core = None
        n, sq, dq = query.shape
        self._add_output((n, sq, embed_dim), query.dtype)
        init = kernel_initializer or GlorotUniform()
        self.w_q = self._add_weight((self.q_dim, dq), init, "wq",
                                    sharded_dim=0)
        self.w_k = self._add_weight((self.kv_dim, key.shape[-1]), init, "wk",
                                    sharded_dim=0)
        self.w_v = self._add_weight((self.kv_dim, value.shape[-1]), init,
                                    "wv", sharded_dim=0)
        self.w_o = self._add_weight((embed_dim, self.q_dim), init, "wo",
                                    sharded_dim=1)
        if self.gate:
            self.w_g = self._add_weight((num_heads, dq), init, "wg",
                                        sharded_dim=0)
        if use_bias:
            self.w_bias = self._add_weight((embed_dim,), ZeroInitializer(),
                                           "bias")
        one = ConstantInitializer(1.0)
        if self.qk_norm is not None:
            self.w_qn = self._add_weight((self.head_dim,), one, "q_norm")
            self.w_kn = self._add_weight((self.head_dim,), one, "k_norm")
        if self.sparse:
            assert causal and self._self_attn and not self.window, (
                "a learned selection needs causal self-attention, no window")
            hi, di = (int(self.sparse["index_heads"]),
                      int(self.sparse["index_dim"]))
            self.index_heads, self.index_dim = hi, di
            self.topk = int(self.sparse["topk"])
            self.index_eps = float(self.sparse.get("eps", 1e-6))
            # the indexer's key as stored: whole lane tiles, the padding
            # zero (what the TPU's tiled memory holds for it anyway)
            self.index_width = -(-di // _LANES) * _LANES
            # the indexer turns its whole head, plainly, at the op's theta
            self.index_rope = (None if self.rope is None else
                               {"rope_theta": self.rope["rope_theta"]})
            # the parts an owner table tells apart (``Op.scopes``); outside
            # them: q/k/v, the K/V write, the output projection
            self.scopes = ("dsa_index", "dsa_select", "dsa_core")
            self.decode_kind = "sparse"
            # {chunk bucket: "mask" | "loop" | "dense"}, as traced
            self.chunk_core = {}
            self.w_iq = self._add_weight((hi * di, dq), init, "wiq",
                                         sharded_dim=0)
            self.w_ik = self._add_weight((di, dq), init, "wik")
            self.w_iw = self._add_weight((hi, dq), init, "wiw")
            self.w_ikn = self._add_weight((di,), one, "ik_norm")
            self.w_ikb = self._add_weight((di,), ZeroInitializer(),
                                          "ik_bias")

    def _wants_ring(self, ctx: OpContext) -> bool:
        pc = self.parallel_config
        mesh = ctx.mesh
        if mesh is None or mesh.axis_size("s") <= 1 or not (
                self._self_attn and self._plain):
            return False
        s_deg = pc.dims[1] if pc is not None and len(pc.dims) >= 2 else (
            mesh.axis_size("s"))
        return (s_deg == mesh.axis_size("s")
                and self.inputs[0].shape[1] % s_deg == 0)

    def _qkv(self, params, xq, xk, xv, ctx, positions=None):
        """The q/k/v projections — ONE implementation shared by
        forward and every kind of serving step (:meth:`serve_step`: a
        prompt chunk, a decode position, a verify window), so the
        cached K/V a decode step attends over carry exactly the bits
        the full-sequence forward would recompute.  ``positions`` (n, s) or
        (s,): where the rows stand, for an op with rotary positions (the
        keys are rotated BEFORE they are cached)."""
        n = xq.shape[0]
        hd = self.head_dim

        def proj(x, w, h):
            y = jnp.einsum("nsi,oi->nso", x, cast_compute(params[w.name], ctx),
                           preferred_element_type=jnp.float32)
            return cast_compute(y, ctx).reshape(n, x.shape[1], h, hd)

        q, k = (proj(xq, self.w_q, self.num_heads),
                proj(xk, self.w_k, self.num_kv_heads))
        if self.qk_norm is not None:    # between projection and rotation
            q = cast_compute(rms_normalize(q, params[self.w_qn.name],
                                           self.qk_norm), ctx)
            k = cast_compute(rms_normalize(k, params[self.w_kn.name],
                                           self.qk_norm), ctx)
        if self.rope is not None:
            q, k = (apply_rope(q, positions, self.rope),
                    apply_rope(k, positions, self.rope))
        return q, k, proj(xv, self.w_v, self.num_kv_heads)

    def _index(self, params, x, positions, ctx):
        """The indexer's three projections of ``x`` (n, s, d), beside
        :meth:`_qkv` and shared like it by forward and every serving step:
        ``(qI (n, s, Hi, di), kI (n, s, di), w (n, s, Hi) f32)`` — ``kI``
        is ONE key head, LayerNorm-ed (scale and bias) before its rotation;
        ``w`` weighs the index heads from the token itself, the two
        ``** -0.5`` factors folded in."""
        with jax.named_scope("dsa_index"):
            n, s, _ = x.shape
            hi, di = self.index_heads, self.index_dim

            def proj(w):
                return jnp.einsum("nsi,oi->nso", x,
                                  cast_compute(params[w.name], ctx),
                                  preferred_element_type=jnp.float32)

            qi = cast_compute(proj(self.w_iq), ctx).reshape(n, s, hi, di)
            kf = proj(self.w_ik)
            mu = jnp.mean(kf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(kf - mu), axis=-1, keepdims=True)
            ki = cast_compute((kf - mu) * jax.lax.rsqrt(var + self.index_eps)
                              * params[self.w_ikn.name]
                              + params[self.w_ikb.name], ctx)
            wi = proj(self.w_iw) * (hi ** -0.5 * di ** -0.5)
            if self.index_rope is not None:
                qi = apply_rope(qi, positions, self.index_rope)
                ki = apply_rope(ki[:, :, None, :], positions,
                                self.index_rope)[:, :, 0, :]
            return qi, ki, wi

    def _out_proj(self, params, attn, n, sq, ctx, xq=None):
        """The context -> embed output projection (+bias), shared by
        forward/prefill/decode like :meth:`_qkv`; the per-head gate, where
        the op has one, is read off the op's input ``xq`` first."""
        if self.gate:
            g = jnp.einsum("nsi,hi->nsh", xq,
                           cast_compute(params[self.w_g.name], ctx),
                           preferred_element_type=jnp.float32)
            attn = attn.reshape(n, sq, self.num_heads, self.head_dim) \
                * jax.nn.sigmoid(g)[..., None]
        attn = cast_compute(attn, ctx).reshape(n, sq, self.q_dim)
        out = jnp.einsum("nsi,oi->nso", attn,
                         cast_compute(params[self.w_o.name], ctx),
                         preferred_element_type=jnp.float32)
        if self.use_bias:
            out = out + params[self.w_bias.name].astype(out.dtype)
        return cast_compute(out, ctx)

    def forward(self, params, inputs, ctx: OpContext):
        xq = cast_compute(inputs[0], ctx)
        xk = xq if self._self_attn else cast_compute(inputs[1], ctx)
        xv = xq if self._self_attn else cast_compute(inputs[2], ctx)
        n, sq, _ = xq.shape
        q, k, v = self._qkv(params, xq, xk, xv, ctx,
                            None if self.rope is None else jnp.arange(sq))
        rng = None
        if ctx.training and self.dropout > 0.0 and ctx.rng is not None:
            rng = jax.random.fold_in(ctx.rng, self.outputs[0].uid)
        keep = None
        if self.sparse and sq > self.topk:
            # the dense core under a mask from the chosen sets (training
            # and ``predict``; the serving steps have forms of their own)
            qi, ki, wi = self._index(params, xq, jnp.arange(sq), ctx)
            pos = jnp.arange(sq)
            with jax.named_scope("dsa_index"):
                scores = jnp.where(pos[None, :] > pos[:, None], NEG_INF,
                                   index_scores(qi, ki, wi))
            with jax.named_scope("dsa_select"):
                keep = selected(scores, pos, *select_threshold(
                    scores, self.topk))[:, None]
        attn = self._attend(q, k, v, ctx, rng, ctx.training,
                            self._wants_ring(ctx), keep)
        return [self._out_proj(params, attn, n, sq, ctx, xq)]

    def _attend(self, q, k, v, ctx: OpContext, rng=None,
                training: bool = False, ring: bool = False, keep=None):
        """The full-sequence attention core, chosen from what the
        operands look like, and noted at trace time in
        ``self.kernel_cores[training]`` (``"ring"``, ``"owned"``,
        ``"library"`` or ``"dense"``) for
        :meth:`FFModel.attention_kernels`."""
        scale = 1.0 / math.sqrt(self.head_dim)
        dropout = self.dropout if training else 0.0
        if ring:
            core = "ring"
            attn = ring_attention(q, k, v, ctx.mesh, self.causal, scale,
                                  dropout, rng)
        elif self._plain and _use_flash(q, k, ctx.flash_attention,
                                        rng is not None, training=training):
            core = _flash_core(q, k, ctx.mesh)
            attn = _flash_attention(q, k, v, self.causal, scale, ctx.mesh)
        else:
            core = "dense"
            with (jax.named_scope("dsa_core") if self.sparse
                  else contextlib.nullcontext()):
                attn = _dense_attention(q, k, v, self.causal, scale, dropout,
                                        rng, self.window, keep)
        self.kernel_cores[training] = core
        return attn

    # ---- paged KV cache (docs/serving.md "Paged KV & prefix caching") --
    def _fold_rows(self, kv):
        """``(..., h, hd)`` K or V rows -> the pool's stored form
        ``(..., h * hd)``: each token's heads side by side in ONE
        lane-dense minor dim (``analysis/kv_memory.py`` says why: with
        ``(h, hd)`` minor no (8, 128) tile is filled, and XLA's TPU
        compiler transposed the whole pool for the scatter, back for
        the donated output, again for the gather and once more for the
        einsums — eight pool-sized copies a layer).  The same bytes in
        the same order, so nothing a CPU parity pin reads changes."""
        return kv.reshape(kv.shape[:-2] + (kv.shape[-2] * kv.shape[-1],))

    def _gather_pages(self, pool, table):
        """Each row of ``table`` (n, pages_per_slot) gathered out of
        the folded ``pool`` back into position order, and THAT view —
        never the pool — unfolded to the ``(n, L, h, hd)`` the
        attention einsums read.  ``mode="clip"``: sentinel table
        entries are OOB by design, and ``jnp.take``'s default "fill"
        would gather NaN, which the exact-zero mask multiplies to NaN,
        not zero."""
        rows = jnp.take(pool, table, axis=0, mode="clip")
        return rows.reshape(table.shape[0], -1, self.num_kv_heads,
                            self.head_dim)

    def serve_state(self, slots, num_pages, page_size, mesh_sizes):
        """A K and a V pool, ``(num_pages, page_size, kv_heads *
        head_dim)``: lane-dense rows (:meth:`_fold_rows`), so that no
        consumer wants the pool in another layout.  Pages are replicated
        over ``n`` (interchangeable across slots); the folded dim is
        sharded over ``c`` like the projections feeding it, where ``c``
        divides the key/value heads (each shard then holds whole heads).
        An op with a ``window`` declares it: its rows are then a ring of
        its own a slot, not pages of the pool (``analysis/kv_memory.
        kv_cache_layout`` reshapes the entry; :meth:`serve_step` reads the
        geometry off the leaves it is handed)."""
        c = (mesh_sizes or {}).get("c", 1)
        c_entry = "c" if (c > 1 and self.num_kv_heads % c == 0) else None
        shape = (num_pages, page_size, self.kv_dim)
        entries = (None, None, c_entry)
        out = {"kind": "kv",
               "shapes": {"k": shape, "v": shape},
               "entries": {"k": entries, "v": entries},
               "dtype": "compute"}
        if self.window:
            out["window"] = self.window
        if self.sparse:
            # a third page-major leaf in the SHARED pool: the indexer's one
            # key head, ``index_dim`` values a token stored ``index_width``
            # wide; and what the op counts of its choosing, on the device
            # (``counts`` rows: queries, of them with a history no longer
            # than ``topk``, positions chosen, positions live; each a
            # ``[high, low]`` pair, :func:`~.common.add_wide`)
            out["shapes"]["ik"] = (num_pages, page_size, self.index_width)
            out["entries"]["ik"] = (None, None, None)
            out["values"] = {"ik": self.index_dim}
            out["counters"] = {"shapes": {"counts": (4, 2)},
                               "entries": {"counts": (None, None)}}
        return out

    def serve_check(self, max_seq):
        if not (self._self_attn and self.causal):
            raise ValueError(
                f"{self.name}: generation needs causal "
                f"self-attention (cross-attention/bidirectional "
                f"blocks cannot decode autoregressively)")

    def serve_step(self, params, inputs, state, where, ctx: OpContext):
        """One step against the paged KV cache, whatever its kind:
        project the positions' Q/K/V, scatter their K/V rows into the
        pages at ``(write page, write row)``, gather each slot's page
        table back into position order and attend over THAT — history
        written by earlier steps (or borrowed from the prefix cache) plus
        the rows just written, causally masked on GLOBAL positions.

        ``state``: ``{"k", "v"}``, the folded ``(num_pages, page, h *
        hd)`` pools, updated in place under donation — no compiled
        serving program copies them (``GraphDecoder.pool_copies``
        counts).  Table entries and write pages at the pool's ``no_page``
        sentinel are OOB by design: such reads are masked, such writes
        dropped (pad rows of a chunk, slots that are not decoding — a
        write through a stale entry could corrupt a SHARED prefix page).
        The kinds differ in how the write indices arrive and in the core:

        * ``"chunk"``: indices computed here from the slot's table row,
          ``start`` and ``length``; :func:`_paged_chunk_attention`, so
          chunked prefill == the monolithic forward row for row (the
          ISSUE 15 parity anchor), pad rows' outputs being garbage the
          caller ignores;
        * ``"token"``: host-computed indices.  Where
          :meth:`_decode_core` says ``"paged"`` (a TPU, one device, a
          geometry :mod:`paged_decode_kernel` takes) nothing is gathered:
          the kernel reads each decoding slot's live pages out of the
          pools where they lie, heads on the folded dim.  Otherwise
          :func:`_decode_attention` fed the gathered cache, bit-identical
          on CPU to the dense forward's row at ``pos``.  Which one was
          traced is noted in ``self.decode_core``
          (``GraphDecoder.decode_attention`` sums it);
        * ``"window"``: host-computed ``(slots, W)`` indices;
          :func:`_verify_window_attention`, each window row bit-identical
          on CPU to the sequential token step at that position (the
          greedy-speculation parity pin).  Rejected rows need no cleanup:
          they stay masked until a later round overwrites them.

        Shares :meth:`_qkv`/:meth:`_out_proj` with forward."""
        xq = cast_compute(inputs[0], ctx)
        n, w, _ = xq.shape
        chunk, token = where.kind == "chunk", where.kind == "token"
        positions = None
        if self.rope is not None or self.window or self.sparse:
            positions = ((where.start + jnp.arange(w))[None] if chunk
                         else where.pos[:, None] + jnp.arange(w)[None, :])
        q, k, v = self._qkv(params, xq, xq, xq, ctx, positions)
        if self.window:
            return self._serve_step_window(params, xq, q, k, v, state,
                                           where, positions, ctx)
        k_pool, v_pool = state["k"], state["v"]
        # a learned selection can leave something out only of a table
        # longer than its ``topk``: under it the op IS dense attention
        if self.sparse and where.table.shape[-1] * k_pool.shape[1] \
                > self.topk:
            return self._serve_step_sparse(params, xq, q, k, v, state,
                                           where, positions, ctx)
        qpos = where.start + jnp.arange(w) if chunk else None
        wp, wr = self._write_indices(where, qpos, k_pool)

        def rows(kv):
            # the new rows as the write indices address them, folded
            return self._fold_rows(kv[0] if chunk else
                                   kv[:, 0] if token else kv)

        def view(pool):
            return self._gather_pages(
                pool, where.table[None] if chunk else where.table)

        k_pool = k_pool.at[wp, wr].set(rows(k), mode="drop")
        v_pool = v_pool.at[wp, wr].set(rows(v), mode="drop")
        scale = 1.0 / math.sqrt(self.head_dim)
        if token:
            self.decode_core = self._decode_core(k_pool, ctx)
        if token and self.decode_core == "paged":
            from .paged_decode_kernel import paged_decode_attention
            attn = paged_decode_attention(
                self._fold_rows(q[:, 0]), k_pool, v_pool, where.table,
                where.pos, wp, self.num_heads, scale, self.num_kv_heads)
        else:
            kg, vg = view(k_pool), view(v_pool)
            if chunk:
                attn = _paged_chunk_attention(q, kg, vg, qpos, scale)
            elif token:
                attn = _decode_attention(q, kg, vg, where.pos, scale)
            else:
                qpos = where.pos[:, None] + jnp.arange(w)[None, :]
                attn = _verify_window_attention(q, kg, vg, qpos, scale)
        new = dict(state, k=k_pool, v=v_pool)
        if self.sparse:     # chose nothing: every live position was read
            if chunk:
                self.chunk_core[w] = "dense"
            new = self._counted(new, where, positions, None)
        return [self._out_proj(params, attn, n, w, ctx, xq)], new

    @staticmethod
    def _write_indices(where, qpos, pool):
        """``(write pages, write rows)`` of a step's new rows: the host's for
        a token step or a window; for a chunk at positions ``qpos`` computed
        here from the slot's table row, pad rows sent to the sentinel."""
        if where.kind != "chunk":
            return where.write_pages, where.write_rows
        no_page, page = pool.shape[:2]
        # mode="clip" everywhere: the sentinel id is OOB by design, and
        # jnp.take's default "fill" mode would gather NaN — which the
        # exact-zero mask multiplies to NaN, not zero
        wp = jnp.take(where.table, qpos // page, mode="clip")
        wp = jnp.where(jnp.arange(qpos.shape[0]) < where.length, wp, no_page)
        return wp, qpos % page

    def _serve_step_window(self, params, xq, q, k, v, state, where,
                           positions, ctx: OpContext):
        """The step of an op with a ``window``, against rows of its own:
        ``state`` leaves are ``(slots, ring_pages, page, kv_heads *
        head_dim)``, a ring of ``R = ring_pages * page`` rows a slot in
        which position ``p`` of slot ``s`` lives at ``(s, (p % R) // page,
        p % page)`` — arithmetic, so the host allocates and sends nothing
        for it.  ``R >= window + the longest chunk`` (the engine sizes it),
        so after a chunk's rows are written every position its first query
        may see is still there.  The ring row ``r`` of a slot whose newest
        position is ``last`` holds position ``last - (last - r) % R``:
        negative for a row this stream has not written (what an earlier
        stream left there is masked, never read as a value).  A chunk and
        the gathered token step attend over the slot's whole ring with
        those positions; the paged kernel copies only the pages that hold
        ``pos - window + 1 .. pos``.  Speculation's verify window is
        refused before it gets here (``GraphDecoder.refusal``)."""
        if where.kind == "window":
            raise NotImplementedError(
                f"{self.name}: a verify window over a windowed cache")
        chunk = where.kind == "chunk"
        n, w = xq.shape[:2]
        k_ring, v_ring = state["k"], state["v"]
        slots, ring_pages, page, e = k_ring.shape
        ring = ring_pages * page
        live = where.live(w)                                  # (n, w)
        slot = (jnp.reshape(where.slot, (1,)) if chunk
                else jnp.arange(slots))
        # dropped writes go past the last slot
        ws = jnp.where(live, slot[:, None], slots)
        wpg = (positions % ring) // page
        wr = positions % page
        k_ring = k_ring.at[ws, wpg, wr].set(self._fold_rows(k), mode="drop")
        v_ring = v_ring.at[ws, wpg, wr].set(self._fold_rows(v), mode="drop")
        scale = 1.0 / math.sqrt(self.head_dim)
        if not chunk:
            self.decode_core = self._decode_core(k_ring, ctx)
        if not chunk and self.decode_core == "paged":
            from .paged_decode_kernel import paged_decode_attention
            table = (jnp.arange(slots, dtype=jnp.int32)[:, None] * ring_pages
                     + jnp.arange(ring_pages, dtype=jnp.int32)[None, :])
            pages = slots * ring_pages
            attn = paged_decode_attention(
                self._fold_rows(q[:, 0]), k_ring.reshape(pages, page, e),
                v_ring.reshape(pages, page, e), table, where.pos,
                jnp.where(live[:, 0], 0, pages).astype(jnp.int32),
                self.num_heads, scale, self.num_kv_heads, self.window)
        else:
            last = positions[:, -1] if not chunk else (
                where.start + where.length - 1)[None]
            r = jnp.arange(ring)[None, :]
            kpos = last[:, None] - (last[:, None] - r) % ring  # (n, ring)

            def view(pool):
                rows = jnp.take(pool, slot, axis=0, mode="clip")
                return rows.reshape(n, ring, self.num_kv_heads,
                                    self.head_dim)

            kg, vg = view(k_ring), view(v_ring)
            if chunk:
                attn = _paged_chunk_attention(q, kg, vg, positions[0], scale,
                                              kpos[0], self.window)
            else:
                attn = _decode_attention(q, kg, vg, where.pos, scale, kpos,
                                         self.window)
        return ([self._out_proj(params, attn, n, w, ctx, xq)],
                {"k": k_ring, "v": v_ring})


    def _serve_step_sparse(self, params, xq, q, k, v, state, where,
                           positions, ctx: OpContext):
        """The step of an op with a learned selection (``sparse=``) over a
        table longer than ``topk``.  ``state``: ``{"k", "v", "ik",
        "counts"}`` — the indexer's key of every position is written to
        ``ik`` with the indices that write ``k`` and ``v``, so it pages,
        shares prefixes, rolls back and migrates with them.  Each query
        scores every live position of its slot (``dsa_index``:
        :func:`index_scores` in f32 against the rows of ``ik``), chooses its
        ``topk`` best (``dsa_select``: EXACT, of equal scores the lower
        position first; every live position while there are no more than
        ``topk``) and attends over the chosen ones only (``dsa_core``).
        The forms, read off the step's kind and the shapes at trace time,
        never a flag:

        * ``"chunk"`` over a table of one key block (``_KEY_BLOCK``) at
          most: :func:`_paged_chunk_attention` under the chosen sets as a
          MASK (``chunk_core`` ``"mask"``), bit for bit the dense op while
          the history is under ``topk``; over a longer table
          :meth:`_sparse_over_blocks` (``"loop"``): scores and core a block
          of keys at a time, never ``(heads, chunk, max_seq)`` at once;
        * ``"token"``: where :meth:`_decode_core` says the in-place read
          applies (a TPU, one device) the set is CHOSEN by a kernel that
          reads ``ik`` in place (:meth:`_chosen_set`) and the core is one of
          two (:meth:`_token_form`, on the table's shape).  A table of no
          more pages than the op chooses rows (``pages_per_slot <= topk``):
          the paged decode kernel reads every live page where it lies,
          once, under the chosen set as a MASK (``decode_core``
          ``"paged"``, :meth:`_sparse_paged`: no list of rows, nothing
          gathered).  A longer table: the chosen ROWS are copied out of the
          pools, ``topk`` a slot, and attended over densely (``"rows"``,
          :meth:`_sparse_rows`: the core then reads ``topk`` rows a slot
          whatever the history).  Elsewhere the slot's whole view under a
          mask (``"gathered"``), bit for bit the dense op under ``topk``;
        * ``"window"``: each row its own set, as a mask over the views,
          bit for bit on the CPU the sequential token step's."""
        n, w = xq.shape[:2]
        chunk, token = where.kind == "chunk", where.kind == "token"
        k_pool, v_pool, i_pool = state["k"], state["v"], state["ik"]
        table = where.table[None] if chunk else where.table      # (n, pps)
        L = table.shape[1] * k_pool.shape[1]
        wp, wr = self._write_indices(where, positions[0] if chunk else None,
                                     k_pool)

        def rows(x):    # the new rows as the write indices address them
            return x[0] if chunk else x[:, 0] if token else x

        k_pool = k_pool.at[wp, wr].set(rows(self._fold_rows(k)), mode="drop")
        v_pool = v_pool.at[wp, wr].set(rows(self._fold_rows(v)), mode="drop")
        qi, ki, wi = self._index(params, xq, positions, ctx)
        with jax.named_scope("dsa_index"):
            pad = self.index_width - self.index_dim
            i_pool = i_pool.at[wp, wr].set(
                rows(jnp.pad(ki, ((0, 0), (0, 0), (0, pad)))), mode="drop")
        scale = 1.0 / math.sqrt(self.head_dim)
        new = dict(state, k=k_pool, v=v_pool, ik=i_pool)

        def index_keys():   # (n, L, di): each slot's view of ``ik``
            g = jnp.take(i_pool, table, axis=0, mode="clip")
            return g.reshape(n, L, -1)[..., :self.index_dim]

        if token:
            self.decode_core = self._token_form(k_pool, table, ctx)
        if chunk:
            self.chunk_core[w] = "loop" if L > _KEY_BLOCK else "mask"
        if chunk and L > _KEY_BLOCK:
            attn, chosen = self._sparse_over_blocks(
                q, qi, wi, k_pool, v_pool, i_pool, where, scale)
        elif token and self.decode_core == "paged":
            attn, chosen = self._sparse_paged(q, qi, wi, k_pool, v_pool,
                                              i_pool, where, scale)
        elif token and self.decode_core == "rows":
            attn, chosen = self._sparse_rows(q, qi, wi, k_pool, v_pool,
                                             i_pool, where, scale)
        else:
            kpos = jnp.arange(L)
            with jax.named_scope("dsa_index"):
                # (a token step's one query twice, row 0 kept: a one-row
                # product drifts an ulp from a window's, _decode_attention)
                dup = 2 if token else 1
                scores = index_scores(jnp.tile(qi, (1, dup, 1, 1)),
                                      index_keys(),
                                      jnp.tile(wi, (1, dup, 1)))[:, :w]
                scores = jnp.where(kpos[None, None, :]
                                   > positions[:, :, None], NEG_INF, scores)
            with jax.named_scope("dsa_select"):
                keep = selected(scores, kpos, *select_threshold(
                    scores, self.topk))
                chosen = jnp.sum(keep & (scores > NEG_INF / 2)
                                 & where.live(w)[:, :, None])
                keep = keep[:, None]                        # (n, 1, w, L)
            with jax.named_scope("dsa_core"):
                kg = self._gather_pages(k_pool, table)
                vg = self._gather_pages(v_pool, table)
                if chunk:
                    attn = _paged_chunk_attention(q, kg, vg, positions[0],
                                                  scale, keep=keep)
                elif token:
                    attn = _decode_attention(q, kg, vg, where.pos, scale,
                                             keep=keep)
                else:
                    attn = _verify_window_attention(q, kg, vg, positions,
                                                    scale, keep=keep)
        return ([self._out_proj(params, attn, n, w, ctx, xq)],
                self._counted(new, where, positions, chosen))

    def _counted(self, state, where, positions, chosen):
        """``state`` with the step added to the op's counters, where the
        engine keeps them (``counts``: queries, of them with no more than
        ``topk`` live positions, positions ``chosen`` (``None``: all that
        were live), positions live; live rows of the step only)."""
        if "counts" not in state:
            return state
        live = where.live(positions.shape[1])
        seen = jnp.where(live, positions + 1, 0)
        step = jnp.stack([
            jnp.sum(live), jnp.sum(live & (seen <= self.topk)),
            jnp.sum(seen) if chosen is None else chosen, jnp.sum(seen)])
        return dict(state, counts=add_wide(state["counts"],
                                           step.astype(jnp.int32)))

    def selection_stats(self, counts):
        """The op's counters as fetched (``counts`` (4, 2), :meth:`_counted`)
        -> ``{"topk", "queries", "dense_queries", "chosen_mean",
        "live_mean"}``: a mean is over the live queries."""
        queries, dense, chosen, live = read_wide(counts)
        return {"topk": self.topk, "queries": queries,
                "dense_queries": dense,
                "chosen_mean": chosen / queries if queries else 0.0,
                "live_mean": live / queries if queries else 0.0}

    def _token_form(self, pool, table, ctx: OpContext) -> str:
        """The form of a sparse op's token step, from what the code can see
        (never a flag): ``"gathered"`` where the in-place read does not
        apply (:meth:`_decode_core`); else ``"paged"`` where a slot's
        ``table`` has no more pages than the op chooses rows, ``"rows"``
        beyond.  Reading pages costs by the HISTORY, reading rows by
        ``topk``, and on this chip a copy costs by its count, a 16 KB page
        about what a 1 KB row does: with ``pages_per_slot <= topk`` the page
        reader can never issue more copies a slot and pool than the row form
        gathers rows."""
        if self._decode_core(pool, ctx) != "paged":
            return "gathered"
        return "paged" if table.shape[1] <= self.topk else "rows"

    def _sparse_paged(self, q, qi, wi, k_pool, v_pool, i_pool, where, scale):
        """A token step that reads its slot's live PAGES once, where they
        lie, under the chosen set as a mask
        (``paged_decode_kernel.paged_sparse_attention``): the set is never
        turned into a list and no row is copied out of a pool.  The softmax
        runs over exactly the chosen positions at or under ``pos``, float32
        statistics, as the paged kernel keeps them everywhere.  -> (attention
        (n, h * hd) f32 folded, zero for a slot that is not decoding; the
        count of live positions chosen by decoding slots)."""
        from .paged_decode_kernel import paged_sparse_attention
        keep, chosen = self._chosen_set(qi, wi, i_pool, where)
        with jax.named_scope("dsa_core"):
            attn = paged_sparse_attention(
                self._fold_rows(q[:, 0]), k_pool, v_pool, where.table,
                where.pos, where.write_pages, keep, self.num_heads, scale,
                self.num_kv_heads)
        return attn, chosen

    def _sparse_rows(self, q, qi, wi, k_pool, v_pool, i_pool, where, scale):
        """A token step that COPIES the chosen rows: each slot's one query
        scores its slot's positions, the ``topk`` best are named as a LIST
        (:meth:`_chosen_rows`), their K and V rows are gathered out of the
        pools where they lie (``topk`` rows a slot, whatever the history)
        and :func:`_decode_attention` runs over them, each row at the
        position it holds; a slot with fewer live positions than ``topk``
        gets dead rows, masked.  -> (attention (n, 1, h, d), the count of
        live positions chosen by decoding slots)."""
        n, page = q.shape[0], k_pool.shape[1]
        idx, pid, alive, chosen = self._chosen_rows(qi, wi, i_pool, where)
        with jax.named_scope("dsa_select"):
            # a dead row stands at a position no query reaches
            kpos = jnp.where(alive, idx, jnp.iinfo(jnp.int32).max)
        with jax.named_scope("dsa_core"):
            def rows(pool):
                g = pool.at[pid, idx % page].get(mode="clip")
                return g.reshape(n, self.topk, self.num_kv_heads,
                                 self.head_dim)
            attn = _decode_attention(q, rows(k_pool), rows(v_pool),
                                     where.pos, scale, kpos=kpos)
        return attn, chosen

    def _chosen_set(self, qi, wi, i_pool, where):
        """The choice of a token step on one TPU -> ``(keep (n, L) bool: the
        chosen positions of each slot's table, the count of live positions
        chosen by decoding slots)``.  Scores and threshold in ONE kernel that
        reads the live pages of ``i_pool`` in place, a slot's score row in
        VMEM (``dsa_index``: :mod:`paged_index_kernel`, which takes every
        ``ik`` leaf beside a pool that :meth:`_decode_core` takes: the same
        dtype and page, a row of whole lane tiles), then the set as a mask
        (``dsa_select``: :func:`selected`); a slot that does not decode reads
        nothing and keeps dead positions.  EXACT for the scores the kernel
        makes, of equal scores the lower position first: the set
        ``jax.lax.top_k`` names on them.  (The scores are
        :func:`index_scores`' arithmetic, bit for bit under the CPU's
        interpreter; on the chip the kernel's products sum in another order
        than XLA's over a view, the same precision and not the same bits,
        so of positions that all but tie another may be the 2 048-th.)"""
        from .paged_index_kernel import paged_index_select
        with jax.named_scope("dsa_index"):
            scores, thr, last = paged_index_select(
                qi[:, 0], wi[:, 0], i_pool, where.table, where.pos,
                where.write_pages, self.topk)
        with jax.named_scope("dsa_select"):
            keep = selected(scores, jnp.arange(scores.shape[1]), thr, last)
            chosen = jnp.sum(keep & (scores > NEG_INF / 2) & where.live(1))
        return keep, chosen

    def _chosen_rows(self, qi, wi, i_pool, where):
        """The choice of a token step's ``"rows"`` form -> ``(idx (n, topk)
        int32: the chosen positions in any order, pid (n, topk): the page of
        the slot's table that holds each, alive (n, topk): which of them a
        decoding slot's query may see, the count of live positions chosen
        by decoding slots)``: :meth:`_chosen_set`, then the set as a LIST by
        rank (``dsa_select``: ``rows_by_rank``, no sort and no scatter) and
        each position's page from the table; a slot that does not decode
        gets dead rows."""
        from .paged_index_kernel import rows_by_rank
        keep, chosen = self._chosen_set(qi, wi, i_pool, where)
        with jax.named_scope("dsa_select"):
            idx = rows_by_rank(keep, self.topk)
            pid = jnp.take_along_axis(where.table, idx // i_pool.shape[1],
                                      axis=1)
            # (a live position's score is finite: ``vals > NEG_INF / 2``)
            alive = (idx <= where.pos[:, None]) & where.live(1)
        return idx, pid, alive, chosen

    def _sparse_over_blocks(self, q, qi, wi, k_pool, v_pool, i_pool, where,
                            scale):
        """A prompt chunk's ``B`` queries against a LONG table, a block of
        ``_KEY_BLOCK`` keys at a time and only the blocks the chunk's last
        real row can see (the scheme of ``LatentAttention.
        _over_key_blocks``): first the indexer's scores into a ``(B, L)``
        f32 buffer (``dsa_index``; the per-head scores live a block long),
        then each row's threshold over the whole of it (``dsa_select``),
        then the core under an online softmax with the chosen set as a MASK
        on blocks read whole (``dsa_core``: f32 statistics, the finite
        ``NEG_INF``, probabilities rounded to the values' dtype).  A row may
        keep nothing of a block, the first one too, so masked entries are
        zeroed explicitly (``exp(NEG_INF - NEG_INF)`` is 1, not 0).
        -> (attention (1, B, h, d) f32, live positions chosen)."""
        B, H, hd = q.shape[1:]
        G = self.num_kv_heads
        page, no_page = k_pool.shape[1], k_pool.shape[0]
        block_pages = max(1, _KEY_BLOCK // page)
        keys = block_pages * page
        pps = where.table.shape[0]
        blocks = -(-pps // block_pages)
        table = jnp.pad(where.table, (0, blocks * block_pages - pps),
                        constant_values=no_page)
        qpos = where.start + jnp.arange(B)
        real = jnp.arange(B) < where.length
        last = where.start + jnp.maximum(where.length, 1) - 1
        seen = last // keys + 1

        def block_rows(pool, b):
            pages = jax.lax.dynamic_slice(table, (b * block_pages,),
                                          (block_pages,))
            return jnp.take(pool, pages, axis=0, mode="clip").reshape(
                keys, pool.shape[-1])

        def dead(b):    # (B, keys): what a row may not see of block b
            return (b * keys + jnp.arange(keys))[None, :] > qpos[:, None]

        def score(b, buf):
            with jax.named_scope("dsa_index"):
                ik = block_rows(i_pool, b)[None, :, :self.index_dim]
                s = jnp.where(dead(b), NEG_INF, index_scores(qi, ik, wi)[0])
                return jax.lax.dynamic_update_slice(buf, s, (0, b * keys))

        with jax.named_scope("dsa_index"):
            buf = jnp.full((B, blocks * keys), NEG_INF, jnp.float32)
        buf = jax.lax.fori_loop(0, seen, score, buf)
        with jax.named_scope("dsa_select"):
            thr, top = select_threshold(buf, self.topk)
        qg = q[0].reshape(B, G, H // G, hd)

        def one(b, carry):
            m, l, acc, count = carry
            with jax.named_scope("dsa_select"):
                s_i = jax.lax.dynamic_slice(buf, (0, b * keys), (B, keys))
                kpos = b * keys + jnp.arange(keys)
                keep = selected(s_i, kpos[None, :], thr, top) & ~dead(b)
                count = count + jnp.sum(keep & real[:, None])
            with jax.named_scope("dsa_core"):
                kb = block_rows(k_pool, b).reshape(keys, G, hd)
                vb = block_rows(v_pool, b).reshape(keys, G, hd)
                s = jnp.einsum("qgrd,kgd->grqk", qg, kb,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(keep[None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.where(keep[None, None],
                              jnp.exp(s - m_new[..., None]), 0.0)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(p, axis=-1)
                acc = alpha[..., None] * acc + jnp.einsum(
                    "grqk,kgd->grqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
            return m_new, l, acc, count

        r = H // G
        init = (jnp.full((G, r, B), NEG_INF, jnp.float32),
                jnp.zeros((G, r, B), jnp.float32),
                jnp.zeros((G, r, B, hd), jnp.float32),
                jnp.zeros((), jnp.int32))
        _, l, acc, count = jax.lax.fori_loop(0, seen, one, init)
        with jax.named_scope("dsa_core"):
            # every row, a pad row too, keeps at least one of the positions
            # it sees (a row's topk is never empty), so l > 0; the guard is
            # against a row the loop never reached
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            return (jnp.transpose(out, (2, 0, 1, 3)).reshape(1, B, H, hd),
                    count)

    def _decode_core(self, pool, ctx: OpContext) -> str:
        """``"paged"`` where the token step can read the pool in place
        (:mod:`paged_decode_kernel`, from what the code can see: backend,
        pool dtype, head and page geometry, one device), else
        ``"gathered"``."""
        from . import paged_decode_kernel
        distributed = ctx.mesh is not None and ctx.mesh.is_distributed
        return ("paged" if paged_decode_kernel.supported(
            jax.default_backend(), pool.dtype, self.num_heads,
            self.head_dim, pool.shape[-2], distributed,
            self.num_kv_heads) else "gathered")

    def parallel_dims(self):
        # (n, s, c): sample DP, sequence SP (ring), channel TP (heads)
        return (True, True, True)

    def sub_problem(self, part_degrees):
        # batch/sequence degrees shard the inputs; the head-TP (c) degree
        # is timed CONSERVATIVELY at full width (forward's reshape is tied
        # to num_heads, so a sharded sub-op can't run in isolation) — the
        # measured per-part cost upper-bounds the true c-split cost
        from ..op import pad_degrees, snap_degrees
        dims = pad_degrees(part_degrees, 3)
        dn, ds = dims[0], dims[1]
        in_shapes = []
        for t in self.inputs:
            d = snap_degrees((dn, ds) + (1,) * (t.num_dims - 2), t.shape)
            in_shapes.append(t.sub_shape(d))
        return in_shapes, {w.name: w.shape for w in self.weights}

    def flops(self):
        n, s, d = self.outputs[0].shape
        # q, k, v, o (and gate) projections, at their own widths
        proj = 2 * n * s * sum(w.volume for w in self.weights
                               if len(w.shape) == 2)
        sk = self.inputs[0].shape[1] if self._self_attn else \
            self.inputs[1].shape[1]
        if self.window:
            sk = min(sk, self.window)
        index = 0
        if self.sparse:     # every pair scored, the topk best attended over
            index = 2 * n * s * sk * self.index_heads * self.index_dim
            sk = min(sk, self.topk)
        scores = 2 * 2 * n * s * sk * self.q_dim   # qk^T and probs*v
        return proj + scores + index

    def internal_io_bytes(self, flash_attention=None):
        """Mirrors ``_attend``'s full selection (the cost model must
        charge for the kernel that will actually run): the flash kernel
        needs a plain op (one head count, no window, rotary or gate), no
        attention-prob dropout, 128-aligned seq lens, and a lane-block
        head_dim; ``flash_attention`` False forces dense, True
        forces flash where legal, None = auto (s >= 512 — the TRAINING
        threshold, since the search objective is a training iteration).
        The backend check in ``_use_flash`` is deliberately absent — the
        search costs a TPU run even when it executes on the CPU mesh."""
        n, sq, _ = self.outputs[0].shape
        sk = self.inputs[0].shape[1] if self._self_attn else \
            self.inputs[1].shape[1]
        # (``_plain``: grouped heads, a window, rotary positions or a gate
        # take the dense core in ``_attend`` whatever the shapes are; the
        # window there is a MASK over the whole (sq, sk) score matrix, so
        # the bytes below are not bounded by it as ``flops()`` is)
        flash_legal = (self._plain and self.dropout == 0.0
                       and sq % 128 == 0 and sk % 128 == 0
                       and (self.head_dim < 128 or self.head_dim % 128 == 0))
        if flash_attention is None:
            flash = flash_legal and max(sq, sk) >= 512
        else:
            flash = flash_attention and flash_legal
        if flash:
            return 0  # flash kernel: scores stay in VMEM
        # dense path: f32 scores written + read (softmax) + bf16 probs
        # written + read = 12 B/element.  Calibrated on chip: without
        # this term the attn768 forward under-predicted ~3x; with it the
        # round-5 attn768 row agrees within 5% (seed CalibrationTable,
        # search/calibration_seed.json attention row).
        index = 0
        if self.sparse and sk > self.topk:
            # the indexer's per-head scores and their weighted sum, f32,
            # written and read; the core itself runs dense under a mask
            index = 8 * n * (self.index_heads + 1) * sq * sk
        return 12 * n * self.num_heads * sq * sk + index


class PositionEmbedding(Op):
    """Learned absolute position table added to a (n, s, d) sequence
    (transformer workload support; no reference analogue)."""

    op_type = OpType.EMBEDDING

    def __init__(self, name, input_tensor, max_len=None,
                 kernel_initializer=None):
        super().__init__(name, [input_tensor])
        n, s, d = input_tensor.shape
        self.max_len = max_len or s
        assert self.max_len >= s, (self.max_len, s)
        self._add_output((n, s, d), input_tensor.dtype)
        self.w_table = self._add_weight(
            (self.max_len, d), kernel_initializer or GlorotUniform(), "table")

    def forward(self, params, inputs, ctx: OpContext):
        x = inputs[0]
        table = params[self.w_table.name][: x.shape[1]]
        return [x + cast_compute(table, ctx)[None]]

    def serve_check(self, max_seq):
        if self.max_len < max_seq:
            raise ValueError(
                f"{self.name}: position table holds {self.max_len} "
                f"positions < max_seq {max_seq}")

    def serve_step(self, params, inputs, state, where, ctx: OpContext):
        """The table rows at the step's GLOBAL positions, gathered, where
        ``forward`` slices the table's head: a prompt chunk at ``start ..
        start+B-1`` (pad rows past the table clip; their outputs are
        chunk padding the caller ignores), one position ``pos`` per slot,
        or a window ``pos[i] .. pos[i]+W-1`` per slot.  Row for row the
        values ``forward``'s broadcast adds, so a chunk at offset 0
        covering the whole prompt IS the forward."""
        x = inputs[0]
        table = params[self.w_table.name]
        if where.kind == "chunk":
            pos = where.start + jnp.arange(x.shape[1])
            rows = cast_compute(jnp.take(table, pos, axis=0), ctx)[None]
        elif where.kind == "token":
            rows = cast_compute(jnp.take(table, where.pos, axis=0),
                                ctx)[:, None, :]
        else:
            qpos = where.pos[:, None] + jnp.arange(x.shape[1])[None, :]
            rows = cast_compute(jnp.take(table, qpos, axis=0), ctx)
        return [x + rows], state

    def parallel_dims(self):
        return (True, True, False)

    def flops(self):
        return self.outputs[0].volume
