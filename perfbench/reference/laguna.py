"""The plain reference of the Laguna family (poolside, ``model_type``
``laguna``): a pre-norm decoder whose layers alternate full and sliding-window
attention with their own numbers of query heads over shared key/value heads,
rotary positions in two forms, a per-head output gate, and a feed-forward that
is dense in the first layer and a mixture of routed experts beside a shared
one in the others.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``: no kernel, no cache, no batching trick.  It imports
nothing of the program and takes nothing the program made.  The weights come
from :func:`leaf`, one leaf at a time from the seed's key (a whole model is 3.87 B
parameters at the benchmark's cut, 15.5 GB in float32: it is never held at
once), rounded through the precision the configuration HOLDS them in
(``weight_dtype``, bfloat16) so that program and reference multiply the same
numbers; the benchmark's family installs the same leaves into the program.

Layer ``l`` with ``H_l`` query heads, ``G`` key/value heads of size ``e``::

    a = RMSNorm(x; g1, eps)
    q = a Wq as (H_l, e);  k = a Wk, v = a Wv as (G, e);  q, k = rope_l(q, k)
    head h attends with key/value head h // (H_l / G), scale 1/sqrt(e),
      causal; a sliding layer only over keys j with i - window < j <= i
    o_h = sigmoid(a Wg)_h * attn_h;   x = x + concat(o) Wo
    b = RMSNorm(x; g2, eps);          x = x + F(b)
    F dense:  (silu(b W1) * (b W3)) W2
    F sparse: shared(b) + scale * sum over the k chosen experts p_i expert_i(b)
      p = softmax over ALL router logits b Wr, the k largest kept and
      renormalised to sum to 1; each expert and the shared one that same
      gated SiLU

Final RMSNorm, untied head.  ``rope`` of a sliding layer: the whole head,
``rope_theta`` 10 000, half-split pairing ``(i, i + e/2)``.  Of a full layer:
the first ``partial_rotary_factor`` of the head, pairing within it, YaRN
inverse frequencies (``transformers``' ``_compute_yarn_parameters``), cos and
sin times ``attention_factor``; the other dims pass.

**Assumed**, each a departure a reader can check against the published code
(the configuration file repeats them): (1) ``gating: true`` is a PER-HEAD
sigmoid gate read off the layer's normed input, as the sibling Laguna-S-2.1
of the same ``model_type`` spells it (the published 33.4 B total fits it and
not a per-element gate); (2) the router is softmax, top-k, renormalised, times
``moe_routed_scaling_factor``, the weights on the experts' output; (3) the
shared expert is added ungated; (4) no biases anywhere, no QK-norm; (5) the
weights are random from the seed, N(0, 0.02), norm scales 1.

``rounding`` selects the arithmetic: ``"float32"`` is the reference;
``"float8_e4m3fn"`` is the CONTROL of "How correct is decided": the same
mathematics with both operands of every matrix product (projections, router,
experts, scores, values, head) rounded through ``float8_e4m3fn`` under a
per-tensor scale, the nearest precision below the bfloat16 the configuration
states.  The control has to come out as not correct.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _exact(x):
    return x


def _through_f8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, _F8_MAX / amax, 1.0)
    return (x * scale).astype(_F8).astype(jnp.float32) / scale


def _through_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# "bfloat16" is no control: it is the precision the configuration COMPUTES
# in, for scripts/laguna_expert_flips.py (what that arithmetic alone does to
# the routing and to the served tokens, no program involved)
ROUNDINGS = {"float32": _exact, "float8_e4m3fn": _through_f8,
             "bfloat16": _through_bf16}


# --------------------------------------------------------------------------
# weights from the seed, a leaf at a time
# --------------------------------------------------------------------------
TOP = ("tok_emb", "g_final", "head")
ATTENTION = ("g1", "wq", "wk", "wv", "wg", "wo", "g2")
DENSE = ("w1", "w3", "w2")
SPARSE = ("wr", "e1", "e3", "e2", "s1", "s3", "s2")
NAMES = TOP + ATTENTION + DENSE + SPARSE


def layer_leaves(sz, layer):
    """The names of layer ``layer``'s leaves."""
    return ATTENTION + (SPARSE if sz["layers"][layer]["mlp"] == "sparse"
                        else DENSE)


def leaf_shape(sz, name, layer=None):
    d, e, G, V = sz["d_model"], sz["head_dim"], sz["kv_heads"], sz["vocab"]
    H = sz["layers"][layer]["heads"] if layer is not None else 0
    F, E, f, fs = sz["d_ff"], sz["experts"], sz["expert_ff"], sz["shared_ff"]
    return {"tok_emb": (V, d), "g_final": (d,), "head": (d, V),
            "g1": (d,), "g2": (d,), "wq": (d, H * e), "wk": (d, G * e),
            "wv": (d, G * e), "wg": (d, H), "wo": (H * e, d),
            "w1": (d, F), "w3": (d, F), "w2": (F, d), "wr": (d, E),
            "e1": (E, d, f), "e3": (E, d, f), "e2": (E, f, d),
            "s1": (d, fs), "s3": (d, fs), "s2": (fs, d)}[name]


def base_key(seed):
    """The key every leaf's key is folded from; ``seed`` is any integer,
    folded to 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf(sz, key, name, layer=None):
    """One leaf from :func:`base_key`'s key, float32: N(0, 0.02) rounded
    through the configuration's ``weight_dtype``; norm scales 1.  The
    leaf's own key is folded from ``key`` here, outside the jitted draw,
    so that leaves of one shape share one compiled program."""
    shape = leaf_shape(sz, name, layer)
    if name.startswith("g"):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.fold_in(key, NAMES.index(name)),
                             0 if layer is None else layer + 1)
    return _draw(key, shape, sz["weight_dtype"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, weight_dtype):
    # drawn flat (the bits are the flat index's, whatever the shape): the
    # TPU's compiler takes 32 s over a (256, 2048, 512) draw and 5 s over
    # the same 268 M elements in a row (compiled here for a described v5e)
    w = 0.02 * jax.random.normal(key, (math.prod(shape),), jnp.float32)
    return w.reshape(shape).astype(jnp.dtype(weight_dtype)).astype(
        jnp.float32)


class Params:
    """The seed's weights, made when asked for: ``leaf(name, layer)``,
    ``layer(l)`` (one layer's leaves as a dict)."""

    def __init__(self, sz, seed):
        self.sz, self.seed, self.key = sz, int(seed), base_key(seed)

    def leaf(self, name, layer=None):
        return leaf(self.sz, self.key, name, layer)

    def layer(self, layer):
        return {n: self.leaf(n, layer) for n in layer_leaves(self.sz, layer)}


def init_params(sz, seed):
    return Params(sz, seed)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def rope_tables(rope, head_dim, positions):
    """``(cos, sin, rot)``: ``(s, rot / 2)`` tables at ``positions`` for one
    layer kind's ``rope_parameters`` entry, the factor on them folded in."""
    rot = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope["rope_theta"])
    freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    inv, factor = 1.0 / freqs, 1.0
    if rope.get("rope_type", "default") == "yarn":
        scale = float(rope["factor"])
        orig = float(rope["original_max_position_embeddings"])

        def dim_of(rotations):     # the dimension that turns that often
            return rot * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
        high = min(math.ceil(dim_of(float(rope["beta_slow"]))), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv = inv / scale * ramp + inv * (1.0 - ramp)
        factor = float(rope.get("attention_factor")
                       or 0.1 * math.log(scale) + 1.0)
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor, rot


def apply_rope(x, cos, sin, rot):
    """``x`` (s, h, e): the first ``rot`` dims rotated, pairs (i, i + rot/2)."""
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, x[..., rot:]],
                           axis=-1)


def attention(x, p, sz, layer, rd=_exact, block=512):
    """``x`` (s, d) -> the attention branch (s, d) of a layer like
    ``sz["layers"][layer]``, queries a ``block`` at a time so that the
    float32 scores of a long sequence are never held whole."""
    s, d = x.shape
    spec = sz["layers"][layer]
    H, G, e = spec["heads"], sz["kv_heads"], sz["head_dim"]
    sliding = spec["attention"] == "sliding_attention"

    def mm(a, w):
        return jnp.matmul(rd(a), rd(w), precision=HI)

    a = rms_norm(x, p["g1"], sz["eps"])
    q = mm(a, p["wq"]).reshape(s, H, e)
    k = mm(a, p["wk"]).reshape(s, G, e)
    v = mm(a, p["wv"]).reshape(s, G, e)
    cos, sin, rot = rope_tables(sz["rope"][spec["attention"]], e,
                                np.arange(s))
    q, k = apply_rope(q, cos, sin, rot), apply_rope(k, cos, sin, rot)
    gate = jax.nn.sigmoid(mm(a, p["wg"]))                        # (s, H)
    kk, vv = rd(k), rd(v)
    pad = -s % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, G, H // G, e)
    starts = jnp.arange(qb.shape[0]) * block

    def one(args):
        qi, start = args
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(s)[None, :]
        keep = j <= i
        if sliding:
            keep &= j > i - sz["window"]
        sc = jnp.einsum("qgre,kge->grqk", rd(qi), kk,
                        precision=HI) / math.sqrt(e)
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kge->qgre", rd(w), vv, precision=HI)

    o = jax.lax.map(one, (qb, starts)).reshape(-1, H, e)[:s]
    o = o * gate[:, :, None]
    return mm(o.reshape(s, H * e), p["wo"])


def gated_ffn(b, w1, w3, w2, rd=_exact):
    def mm(a, w):
        return jnp.matmul(rd(a), rd(w), precision=HI)
    return mm(jax.nn.silu(mm(b, w1)) * mm(b, w3), w2)


def route(b, wr, sz, rd=_exact):
    """``(chosen experts (t, k), their weights (t, k))``: softmax over all
    experts, the k largest renormalised, times the routed scale."""
    probs = jax.nn.softmax(jnp.matmul(rd(b), rd(wr), precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, sz["k"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) * sz[
        "routed_scale"]


def moe(b, p, sz, rd=_exact):
    """The sparse feed-forward on ``b`` (t, d): the shared expert, and a
    plain loop over the routed experts, EVERY expert applied to every token
    and kept at the weight the router gave it there (zero where the token
    did not choose it).  That computes ``experts / k`` times what a
    dispatch would and needs no gather, scatter or sort."""
    idx, wts = route(b, p["wr"], sz, rd)

    def one(out, expert):
        ex, e1, e3, e2 = expert
        w = jnp.sum(jnp.where(idx == ex, wts, 0.0), axis=-1)
        return out + w[:, None] * gated_ffn(b, e1, e3, e2, rd), None

    return jax.lax.scan(
        one, gated_ffn(b, p["s1"], p["s3"], p["s2"], rd),
        (jnp.arange(sz["experts"]), p["e1"], p["e3"], p["e2"]))[0]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sparse_jit(x, p, sz_key, rounding):
    sz = json.loads(sz_key)
    return x + moe(rms_norm(x, p["g2"], sz["eps"]), p, sz,
                   ROUNDINGS[rounding])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _attention_jit(x, p, sz_key, layer, rounding):
    return x + attention(x, p, json.loads(sz_key), layer,
                         ROUNDINGS[rounding])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_jit(x, p, sz_key, rounding):
    sz = json.loads(sz_key)
    b = rms_norm(x, p["g2"], sz["eps"])
    return x + gated_ffn(b, p["w1"], p["w3"], p["w2"], ROUNDINGS[rounding])


def layer_forward(xs, p, sz, layer, rounding="float32"):
    """One layer over a LIST of sequences ``xs`` (each (s_i, d)): attention
    a sequence at a time, the feed-forward over all their tokens at once
    (it acts on each token alone)."""
    sz_key = json.dumps(sz, sort_keys=True)
    attn = {k: p[k] for k in ATTENTION}
    # the first layer of this one's attention kind and head count stands
    # for it, so that equal layers share one compiled program
    like = next(i for i, spec in enumerate(sz["layers"]) if all(
        spec[k] == sz["layers"][layer][k] for k in ("attention", "heads")))
    xs = [_attention_jit(x, attn, sz_key, like, rounding) for x in xs]
    if sz["layers"][layer]["mlp"] != "sparse":
        dense = {k: p[k] for k in ("g2",) + DENSE}
        return [_dense_jit(x, dense, sz_key, rounding) for x in xs]
    sparse = {k: p[k] for k in ("g2",) + SPARSE}
    lens = np.cumsum([x.shape[0] for x in xs])[:-1]
    return jnp.split(_sparse_jit(jnp.concatenate(xs, axis=0), sparse, sz_key,
                                 rounding), lens, axis=0)


def hidden(params, sequences, sz, rounding="float32"):
    """Token sequences (each (s_i,) int) -> their final hidden states, a
    layer at a time: one layer's weights are made, used and dropped."""
    emb = params.leaf("tok_emb")
    xs = [jnp.take(emb, jnp.asarray(t, jnp.int32), axis=0)
          for t in sequences]
    del emb
    for layer in range(len(sz["layers"])):
        p = params.layer(layer)
        xs = layer_forward(xs, p, sz, layer, rounding)
        del p
    g = params.leaf("g_final")
    return [rms_norm(x, g, sz["eps"]) for x in xs]


def lm_logits(params, tokens, sz, rounding="float32"):
    """(n, s) tokens -> (n, s, vocab) logits: the whole model at once, for
    sizes where that fits (the CPU tests)."""
    rd = ROUNDINGS[rounding]
    head = params.leaf("head")
    return jnp.stack([jnp.matmul(rd(x), rd(head), precision=HI)
                      for x in hidden(params, list(tokens), sz, rounding)])


# --------------------------------------------------------------------------
# serving: how far below the reference's best a token's logit lies
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(4, 5))
def _gap_rows(x, x_low, head, nxt, rounding, block=512):
    """For one sequence's final hidden states (exact, and in ``rounding``
    arithmetic) and the token that followed each position: the reference's
    best logit there, its logit of the token that followed, and its logit
    of the token the low arithmetic puts first; ``block`` rows of the
    (s, vocab) logits at a time."""
    rd = ROUNDINGS[rounding]
    s = x.shape[0]
    pad = -s % block

    def blocks(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, block) + a.shape[1:])

    low_head = rd(head)

    def one(args):
        xe, xl, nx = args
        ref = jnp.matmul(xe, head, precision=HI)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, nx[:, None], axis=-1)[:, 0]
        if rounding == "float32":
            return best, got, best
        pick = jnp.argmax(jnp.matmul(rd(xl), low_head, precision=HI),
                          axis=-1)
        return best, got, jnp.take_along_axis(ref, pick[:, None],
                                              axis=-1)[:, 0]

    return tuple(a.reshape(-1)[:s] for a in jax.lax.map(
        one, (blocks(x), blocks(x_low), blocks(nxt))))


def served_gaps(sz, seed, requests, rounding="float8_e4m3fn"):
    """``requests`` is a list of ``(prompt, served_tokens)``.  One causal
    forward over prompt + served tokens per request, all requests through a
    layer before the next layer's weights are made.  Returns, per request,
    the gap ``best - logit[served token]`` at every served position, and
    the same gap for the token the CONTROL arithmetic would have served
    there (``rounding``; with ``"float32"`` the control's pass is skipped
    and its gaps read 0)."""
    params = init_params(sz, seed)
    fulls = [np.concatenate([np.asarray(p, np.int32),
                             np.asarray(t, np.int32)]) for p, t in requests]
    # sequences padded to a multiple of 512 positions (a causal model's
    # earlier positions do not see the padding), so that few shapes compile
    padded = [np.pad(f, (0, -len(f) % 512)) for f in fulls]
    xs = hidden(params, padded, sz)
    lows = xs if rounding == "float32" else hidden(params, padded, sz,
                                                   rounding)
    head = params.leaf("head")
    out = []
    for (prompt, _), full, pad, x, xl in zip(requests, fulls, padded, xs,
                                             lows):
        nxt = np.zeros((len(pad),), np.int32)
        nxt[:len(full) - 1] = full[1:]
        best, got, ctrl = (np.asarray(a, np.float64) for a in _gap_rows(
            x, xl, head, jnp.asarray(nxt), rounding))
        # position len(prompt) - 1 predicts served[0]
        rows = slice(len(prompt) - 1, len(full) - 1)
        out.append({"served": best[rows] - got[rows],
                    "control": best[rows] - ctrl[rows]})
    del params, xs, lows, head
    return out
