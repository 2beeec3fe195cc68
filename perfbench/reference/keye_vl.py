"""The plain reference of the Keye-VL-2.0 family's DECODER (Kwai-Keye,
``model_type`` ``KeyeVL2``): a pre-norm decoder whose every layer has
grouped-query attention with an RMSNorm on each query and key head, a learned
INDEXER that chooses the ``topk`` cached positions each query attends over
(DeepSeek sparse attention's lightning indexer), and a mixture of routed
experts with no shared expert and no dense layer.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``: no kernel, no cache, no batching trick; the chosen set
comes from a FULL SORT of a query's scores.  It imports nothing of the program
and takes nothing the program made.  The weights come from :func:`leaf`, one
leaf at a time from the seed's key, rounded through the precision the
configuration HOLDS them in (``weight_dtype``), so that program and reference
multiply the same numbers.

``N(.; g)`` is RMSNorm with scale ``g``, ``LN`` LayerNorm with scale and bias.
Layer ``l``, ``H`` query heads over ``G`` key/value heads of size ``e``::

    a = N(x; g1)
    q = a Wq as (H, e);  k = a Wk, v = a Wv as (G, e)
    q_h <- N(q_h; gq);  k_g <- N(k_g; gk)        one scale each, all heads
    q, k <- rope3(positions)                      (below)
    qI = a WqI as (Hi, di);  kI = LN(a WkI; gik, bik)  (ONE key head)
    w = a Ww * Hi ** -0.5 * di ** -0.5;   qI, kI <- rope(position)
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])            s <= t
    S_t = the topk positions s <= t of largest I[t, s] (all while t < topk;
          of equal scores the lower position first)
    o_h = sum_{s in S_t} softmax_s(q_h . k_g(h)[s] / sqrt(e)) v_g(h)[s]
    y = x + concat(o) Wo;   b = N(y; g2)
    p = softmax(b Wr) over ALL experts; the k largest renormalised to 1
    z = y + sum_k p_k (silu(b W1_k) * (b W3_k)) W2_k

Final RMSNorm, untied head.  **rope3**: the published ``rope_scaling.
mrope_section`` splits a head's ``e / 2`` frequencies into three runs that
turn by a temporal, a height and a width position (half-split pairing ``(i, i
+ e / 2)``, theta ``rope_theta``); a TEXT token has all three equal, which is
the plain rotary, and the traffic is text: :func:`hidden` hands every token
one position for all three.  The indexer turns its whole 64-value head
plainly at the temporal position.

**Assumed**, each listed in the configuration file: QK-norm (the 30B-A3B
decoder's block norms q and k; no key of the config could say so); the
indexer's form (its key LayerNorm-ed, q and k rotated, head weights from the
token itself, ``WqI`` reading ``a`` since the config gives no q rank, bfloat16
and not fp8, LayerNorm eps as ``rms_norm_eps``); ``q_chunk_size`` /
``kv_chunk_size`` are the tiling of an implementation and no part of the
equations; weights N(0, 0.02), scales 1, biases 0.  NOT built: the vision
tower and image tokens' three distinct positions.

``rounding``: ``"float32"`` is the reference; ``"float8_e4m3fn"`` the CONTROL
(both operands of every matrix product, the indexer's too, rounded through
``float8_e4m3fn`` under a per-tensor scale: the nearest precision below the
bfloat16 the configuration states), which has to come out as not correct.
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
# in, for scripts/keye_index_flips.py (what that arithmetic alone does to
# the chosen sets and to the served tokens, no program involved)
ROUNDINGS = {"float32": _exact, "float8_e4m3fn": _through_f8,
             "bfloat16": _through_bf16}


# --------------------------------------------------------------------------
# weights from the seed, a leaf at a time
# --------------------------------------------------------------------------
TOP = ("tok_emb", "g_final", "head")
ATTENTION = ("g1", "wq", "wk", "wv", "gq", "gk", "wo", "wiq", "wik", "wiw",
             "gik", "bik", "g2")
SPARSE = ("wr", "e1", "e3", "e2")
NAMES = TOP + ATTENTION + SPARSE


def layer_leaves(sz, layer):
    """The names of layer ``layer``'s leaves (every layer is alike)."""
    return ATTENTION + SPARSE


def leaf_shape(sz, name, layer=None):
    d, e, G, V = sz["d_model"], sz["head_dim"], sz["kv_heads"], sz["vocab"]
    H = sz["layers"][layer]["heads"] if layer is not None else 0
    Hi, di = sz["index_heads"], sz["index_dim"]
    E, f = sz["experts"], sz["expert_ff"]
    return {"tok_emb": (V, d), "g_final": (d,), "head": (d, V),
            "g1": (d,), "g2": (d,), "gq": (e,), "gk": (e,),
            "wq": (d, H * e), "wk": (d, G * e), "wv": (d, G * e),
            "wo": (H * e, d), "wiq": (d, Hi * di), "wik": (d, di),
            "wiw": (d, Hi), "gik": (di,), "bik": (di,), "wr": (d, E),
            "e1": (E, d, f), "e3": (E, d, f), "e2": (E, f, d)}[name]


def base_key(seed):
    """The key every leaf's key is folded from; ``seed`` is any integer,
    folded to 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf(sz, key, name, layer=None):
    """One leaf from :func:`base_key`'s key, float32: N(0, 0.02) rounded
    through the configuration's ``weight_dtype``; norm scales 1, the
    LayerNorm's bias 0.  The leaf's own key is folded from ``key`` here,
    outside the jitted draw, so that leaves of one shape share one compiled
    program."""
    shape = leaf_shape(sz, name, layer)
    if name.startswith("g"):
        return jnp.ones(shape, jnp.float32)
    if name == "bik":
        return jnp.zeros(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.fold_in(key, NAMES.index(name)),
                             0 if layer is None else layer + 1)
    return _draw(key, shape, sz["weight_dtype"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, weight_dtype):
    # drawn flat: the TPU's compiler is far quicker over a row than over
    # the same elements in three dimensions (reference/laguna.py)
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


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope3(x, positions3, theta, sections):
    """``x`` (s, h, e) rotated in the THREE-SECTION form: ``positions3`` (3,
    s) are a token's temporal, height and width positions, ``sections`` how
    many of the ``e / 2`` frequencies ``theta ** (-2 i / e)`` turn by each,
    in that order; pairs ``(i, i + e / 2)``."""
    e = x.shape[-1]
    assert sum(sections) == e // 2, (sections, e)
    inv = 1.0 / (float(theta) ** (np.arange(0, e, 2, dtype=np.float64) / e))
    stream = np.repeat(np.arange(3), sections)               # (e / 2,)
    pos = jnp.asarray(positions3, jnp.float32)[stream, :].T  # (s, e / 2)
    ang = pos * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rope(x, positions, theta):
    """The plain rotary: ``x`` (s, h, e), all ``e`` dims at ``positions``
    (s,), pairs ``(i, i + e / 2)``."""
    e = x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, e, 2, dtype=np.float64) / e))
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def chosen(scores, topk):
    """``scores`` (q, s) float32, ``-inf`` where a query may not look ->
    the mask of each row's ``topk`` largest (all the finite ones where there
    are no more), of equal scores the lower position first.  By a FULL SORT
    of every row: the ``topk``-th value, what lies over it, and of what
    equals it the first few by position."""
    s = scores.shape[-1]
    finite = scores > -jnp.inf
    if s <= topk:
        return finite
    thr = jnp.sort(scores, axis=-1)[:, s - topk][:, None]
    over, at = scores > thr, scores == thr
    need = topk - jnp.sum(over, axis=-1, keepdims=True)
    return finite & (over | (at & (jnp.cumsum(at, axis=-1) <= need)))


def index_scores(a, p, sz, positions, rd=_exact):
    """The indexer of the normed input ``a`` (s, d): ``(qI (s, Hi, di), kI
    (s, di), w (s, Hi))``, rotated, the head weights scaled."""
    s = a.shape[0]
    Hi, di = sz["index_heads"], sz["index_dim"]

    def mm(x, w):
        return jnp.matmul(rd(x), rd(w), precision=HI)

    qi = rope(mm(a, p["wiq"]).reshape(s, Hi, di), positions, sz["rope_theta"])
    ki = layer_norm(mm(a, p["wik"]), p["gik"], p["bik"], sz["index_eps"])
    ki = rope(ki[:, None, :], positions, sz["rope_theta"])[:, 0, :]
    return qi, ki, mm(a, p["wiw"]) * (Hi ** -0.5 * di ** -0.5)


def block_keep(qj, wj, ki, start, topk, rd=_exact):
    """The chosen sets of one block of queries, ``(block, s)`` bool: ``qj``
    (block, Hi, di) and ``wj`` (block, Hi) of the queries at positions
    ``start ..``, ``ki`` (s, di) every position's indexer key (already
    through ``rd``)."""
    i = start + jnp.arange(qj.shape[0])[:, None]
    j = jnp.arange(ki.shape[0])[None, :]
    heads = jnp.einsum("qhd,kd->qhk", rd(qj), ki, precision=HI)
    scores = jnp.sum(jax.nn.relu(heads) * wj[:, :, None], axis=1)
    return chosen(jnp.where(j <= i, scores, -jnp.inf), topk)


def chosen_sets(x, p, sz, rd=_exact, block=128):
    """``(s, s)`` bool: row ``t`` marks the positions query ``t`` of the
    sequence ``x`` (s, d) attends over in a layer with leaves ``p`` (for
    ``scripts/keye_index_flips.py``; :func:`attention` computes the same,
    block by block, and does not keep it)."""
    s = x.shape[0]
    a = rms_norm(x, p["g1"], sz["eps"])
    qi, ki, wi = index_scores(a, p, sz, np.arange(s), rd)
    pad = -s % block

    def blocks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (-1, block) + t.shape[1:])

    kki = rd(ki)
    keep = jax.lax.map(
        lambda args: block_keep(args[0], args[1], kki, args[2], sz["topk"],
                                rd),
        (blocks(qi), blocks(wi), jnp.arange(-(-s // block)) * block))
    return keep.reshape(-1, s)[:s]


def attention(x, p, sz, layer, rd=_exact, block=128, positions3=None):
    """``x`` (s, d) -> the attention branch (s, d), queries a ``block`` at a
    time so that neither the heads' nor the indexer's float32 scores of a
    long sequence are held whole.  ``positions3`` (3, s): the three position
    streams (a text token's are equal: the default)."""
    s, d = x.shape
    H, G, e = sz["layers"][layer]["heads"], sz["kv_heads"], sz["head_dim"]
    pos = np.arange(s)
    if positions3 is None:
        positions3 = np.stack([pos, pos, pos])

    def mm(a, w):
        return jnp.matmul(rd(a), rd(w), precision=HI)

    a = rms_norm(x, p["g1"], sz["eps"])
    q = rms_norm(mm(a, p["wq"]).reshape(s, H, e), p["gq"], sz["eps"])
    k = rms_norm(mm(a, p["wk"]).reshape(s, G, e), p["gk"], sz["eps"])
    v = mm(a, p["wv"]).reshape(s, G, e)
    q = rope3(q, positions3, sz["rope_theta"], sz["mrope_section"])
    k = rope3(k, positions3, sz["rope_theta"], sz["mrope_section"])
    qi, ki, wi = index_scores(a, p, sz, positions3[0], rd)
    kk, vv, kki = rd(k), rd(v), rd(ki)
    pad = -s % block

    def blocks(t):
        return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(
            (-1, block) + t.shape[1:])

    qb = blocks(q).reshape(-1, block, G, H // G, e)
    starts = jnp.arange(qb.shape[0]) * block

    def one(args):
        qh, qj, wj, start = args
        keep = block_keep(qj, wj, kki, start, sz["topk"], rd)
        sc = jnp.einsum("qgre,kge->grqk", rd(qh), kk,
                        precision=HI) / math.sqrt(e)
        w = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kge->qgre", rd(w), vv, precision=HI)

    o = jax.lax.map(one, (qb, blocks(qi), blocks(wi), starts))
    return mm(o.reshape(-1, H * e)[:s], p["wo"])


def gated_ffn(b, w1, w3, w2, rd=_exact):
    def mm(a, w):
        return jnp.matmul(rd(a), rd(w), precision=HI)
    return mm(jax.nn.silu(mm(b, w1)) * mm(b, w3), w2)


def route(b, wr, sz, rd=_exact):
    """``(chosen experts (t, k), their weights (t, k))``: softmax over all
    experts, the k largest renormalised to sum to 1."""
    probs = jax.nn.softmax(jnp.matmul(rd(b), rd(wr), precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, sz["k"])
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


def moe(b, p, sz, rd=_exact):
    """The sparse feed-forward on ``b`` (t, d): a plain loop over the routed
    experts, EVERY expert applied to every token and kept at the weight the
    router gave it there (zero where the token did not choose it)."""
    idx, wts = route(b, p["wr"], sz, rd)

    def one(out, expert):
        ex, e1, e3, e2 = expert
        w = jnp.sum(jnp.where(idx == ex, wts, 0.0), axis=-1)
        return out + w[:, None] * gated_ffn(b, e1, e3, e2, rd), None

    return jax.lax.scan(one, jnp.zeros_like(b),
                        (jnp.arange(sz["experts"]), p["e1"], p["e3"],
                         p["e2"]))[0]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _sparse_jit(x, p, sz_key, rounding):
    sz = json.loads(sz_key)
    return x + moe(rms_norm(x, p["g2"], sz["eps"]), p, sz,
                   ROUNDINGS[rounding])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention_jit(x, p, sz_key, rounding):
    return x + attention(x, p, json.loads(sz_key), 0, ROUNDINGS[rounding])


def layer_forward(xs, p, sz, layer, rounding="float32"):
    """One layer over a LIST of sequences ``xs`` (each (s_i, d)): attention
    a sequence at a time, the experts over all their tokens at once (they
    act on each token alone).  Every layer is like layer 0, so all share
    one compiled program a shape."""
    sz_key = json.dumps(sz, sort_keys=True)
    assert sz["layers"][layer] == sz["layers"][0], layer
    attn = {k: p[k] for k in ATTENTION}
    xs = [_attention_jit(x, attn, sz_key, rounding) for x in xs]
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


PAD_TO = 2048   # positions a compared sequence is padded to a multiple of


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
    # padded so that few shapes compile (a causal model's earlier positions
    # neither see the padding nor choose it)
    pad_to = min(PAD_TO, sz["positions"])
    padded = [np.pad(f, (0, -len(f) % pad_to)) for f in fulls]
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
