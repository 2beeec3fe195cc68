"""The plain reference of the openPangu-Ultra-MoE family (``model_type``
``pangu_ultra_moe``): a decoder with multi-head LATENT attention (low-rank
queries with a norm inside, one compressed key/value row a token beside one
rotary row all heads share), SANDWICH norms (each sublayer's output is normed
before the residual add, as well as its input), a dense feed-forward in the
leading layer(s) and, in the others, routed experts chosen by a SIGMOID router
beside a shared expert.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``: no kernel, no cache, no batching trick, and the
EXPANDED form of the attention only (the program's absorbed token step is
held to it).  It imports nothing of the program and takes nothing the program
made.  The weights come from :func:`leaf`, one leaf at a time from the seed's
key, rounded through the precision the configuration HOLDS them in
(``weight_dtype``), so that program and reference multiply the same numbers.

``N(.; g)`` is RMSNorm with scale ``g``.  Layer ``l``, ``H`` heads::

    a = N(x; g1)
    c_q = N(a Wqa; gq);  q = c_q Wqb as (H, nope + rope) = [q_nope | q_pe]
    [c_kv | k_pe] = a Wkva;  c = N(c_kv; gkv);  q_pe, k_pe <- rope(positions)
    [k_nope_h | v_h] = c Wkvb as (H, nope + v);  k_pe is one row for all heads
    s_h(t, u) = (q_nope_h(t).k_nope_h(u) + q_pe_h(t).k_pe(u)) / sqrt(nope + rope)
    o_h = sum_{u <= t} softmax_u(s_h) v_h(u);   A = concat_h(o_h) Wo
    h = x + N(A; g2);   b = N(h; g3);   y = h + N(F(b); g4)
    F dense:  (silu(b W1) * (b W3)) W2
    F sparse: r = sigmoid(b Wr) over ALL ``router_experts``; I = the k largest;
              w_i = routed_scale * r_i / (sum_{j in I} r_j + 1e-20)
              F = Shared(b) + sum_{i in I, i HELD} w_i E_i(b)

Final RMSNorm, untied head over the ``vocab`` rows HELD.

**A share of the model.**  ``sz["experts"]`` experts starting at
``sz["first_expert"]`` are HELD of ``sz["router_experts"]``: the router scores
all of them and keeps its ``k`` choices, and a choice that falls on an expert
held elsewhere adds nothing here, as on one chip of an expert-parallel
deployment.  An expert's weights are drawn from a key of ITS OWN number, so a
share holds the very experts the uncut model (``experts ==
router_experts``) has under those numbers, and the routed parts of disjoint
shares that cover all experts add up to the uncut layer (the CPU tests hold
the program and this file to that).  ``vocab`` likewise is the slice of the
vocabulary held (token ids below it).

**Departures from the published description**, each listed in the
configuration file under ``assumed``: (1) sigmoid scores with no correction
bias and no expert groups (the published config has no such key); (2) the
top-k renormalised, then times ``routed_scaling_factor``; (3) the sandwich
norms placed as above; (4) rotary pairing half-split ``(i, i + rope / 2)``,
softmax scale ``(nope + rope) ** -0.5`` with no YaRN factor; (5) weights
random from the seed, N(0, 0.02), norm scales 1; (6) no multi-token
prediction layer (it belongs to the last pipeline stage and the main model's
logits do not depend on it).

``rounding``: ``"float32"`` is the reference; ``"float8_e4m3fn"`` is the
CONTROL (both operands of every matrix product rounded through
``float8_e4m3fn`` under a per-tensor scale, the nearest precision below the
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
# in (what that arithmetic alone does, no program involved)
ROUNDINGS = {"float32": _exact, "float8_e4m3fn": _through_f8,
             "bfloat16": _through_bf16}


# --------------------------------------------------------------------------
# weights from the seed, a leaf at a time
# --------------------------------------------------------------------------
TOP = ("tok_emb", "g_final", "head")
ATTENTION = ("g1", "wqa", "gq", "wqb", "wkva", "gkv", "wkvb", "wo", "g2",
             "g3", "g4")
DENSE = ("w1", "w3", "w2")
SPARSE = ("wr", "e1", "e3", "e2", "s1", "s3", "s2")
NAMES = TOP + ATTENTION + DENSE + SPARSE
EXPERTS = ("e1", "e3", "e2")       # drawn an expert at a time


def layer_leaves(sz, layer):
    return ATTENTION + (SPARSE if sz["layers"][layer]["mlp"] == "sparse"
                        else DENSE)


def leaf_shape(sz, name, layer=None):
    d, V = sz["d_model"], sz["vocab"]
    H = sz["layers"][layer]["heads"] if layer is not None else 0
    qr, kr = sz["q_rank"], sz["kv_rank"]
    nope, rope, v = sz["nope"], sz["rope"], sz["v"]
    F, E, R = sz["d_ff"], sz["experts"], sz["router_experts"]
    f, fs = sz["expert_ff"], sz["shared_ff"]
    return {"tok_emb": (V, d), "g_final": (d,), "head": (d, V),
            "g1": (d,), "g2": (d,), "g3": (d,), "g4": (d,),
            "gq": (qr,), "gkv": (kr,),
            "wqa": (d, qr), "wqb": (qr, H * (nope + rope)),
            "wkva": (d, kr + rope), "wkvb": (kr, H * (nope + v)),
            "wo": (H * v, d),
            "w1": (d, F), "w3": (d, F), "w2": (F, d), "wr": (d, R),
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
    through ``weight_dtype``; norm scales 1.  A stack of experts is drawn an
    expert at a time from keys folded with the experts' OWN numbers
    (``first_expert ..``), so that every share of one model holds the same
    experts."""
    shape = leaf_shape(sz, name, layer)
    if name.startswith("g"):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.fold_in(key, NAMES.index(name)),
                             0 if layer is None else layer + 1)
    if name in EXPERTS:
        keys = jnp.stack([jax.random.fold_in(key, sz["first_expert"] + i)
                          for i in range(shape[0])])
        return _draw_each(keys, shape[1:], sz["weight_dtype"])
    return _draw(key, shape, sz["weight_dtype"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, weight_dtype):
    # drawn flat: the TPU's compiler is far quicker over a row than over
    # the same elements in three dimensions (reference/laguna.py)
    w = 0.02 * jax.random.normal(key, (math.prod(shape),), jnp.float32)
    return w.reshape(shape).astype(jnp.dtype(weight_dtype)).astype(
        jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_each(keys, shape, weight_dtype):
    return jax.lax.map(lambda k: _draw(k, shape, weight_dtype), keys)


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


def rope(x, positions, theta):
    """``x`` (s, .., r) rotated at ``positions`` (s,): all ``r`` dims,
    pairs ``(i, i + r / 2)``, inverse frequencies ``theta ** (-2 i / r)``."""
    r = x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, r, 2, dtype=np.float64) / r))
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(a, p, sz, layer, rd=_exact, block=128, head_block=32):
    """The normed input ``a`` (s, d) -> the attention sublayer ``A`` (s, d)
    (before its output norm), EXPANDED: ``head_block`` heads at a time and
    within them ``block`` queries at a time, so that the float32 keys,
    values and scores of a 12 672-token request are never held whole."""
    s, _ = a.shape
    H = sz["layers"][layer]["heads"]
    nope, rp, v, kr = sz["nope"], sz["rope"], sz["v"], sz["kv_rank"]

    def mm(x, w):
        return jnp.matmul(rd(x), rd(w), precision=HI)

    pos = np.arange(s)
    cq = rms_norm(mm(a, p["wqa"]), p["gq"], sz["eps"])
    q = mm(cq, p["wqb"]).reshape(s, H, nope + rp)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, sz["rope_theta"])], axis=-1)
    ckv = mm(a, p["wkva"])
    c = rms_norm(ckv[:, :kr], p["gkv"], sz["eps"])
    k_pe = rope(ckv[:, kr:], pos, sz["rope_theta"])              # (s, rope)
    hb = min(head_block, H)
    assert H % hb == 0, (H, hb)
    wkvb = p["wkvb"].reshape(kr, H // hb, hb, nope + v).transpose(1, 0, 2, 3)
    qg = q.reshape(s, H // hb, hb, nope + rp).transpose(1, 0, 2, 3)
    pad = -s % block
    scale = 1.0 / math.sqrt(nope + rp)

    def heads(args):
        w, qh = args                       # (kr, hb, nope + v), (s, hb, e)
        kv = mm(c, w.reshape(kr, -1)).reshape(s, hb, nope + v)
        k = rd(jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, None, :], (s, hb, rp))], axis=-1))
        vv = rd(kv[..., nope:])
        qb = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, block, hb, nope + rp)

        def one(args):
            qi, start = args
            i = start + jnp.arange(block)[:, None]
            j = jnp.arange(s)[None, :]
            sc = jnp.einsum("qhe,khe->hqk", rd(qi), k, precision=HI) * scale
            sc = jnp.where((j <= i)[None], sc, -jnp.inf)
            return jnp.einsum("hqk,khv->qhv", rd(jax.nn.softmax(sc, axis=-1)),
                              vv, precision=HI)

        return jax.lax.map(one, (qb, jnp.arange(qb.shape[0]) * block)
                           ).reshape(-1, hb, v)[:s]

    o = jax.lax.map(heads, (wkvb, qg))                     # (H/hb, s, hb, v)
    return mm(o.transpose(1, 0, 2, 3).reshape(s, H * v), p["wo"])


def gated_ffn(b, w1, w3, w2, rd=_exact):
    def mm(a, w):
        return jnp.matmul(rd(a), rd(w), precision=HI)
    return mm(jax.nn.silu(mm(b, w1)) * mm(b, w3), w2)


def route(b, wr, sz, rd=_exact):
    """``(chosen experts (t, k), their weights (t, k))``: sigmoid of every
    router logit, the k largest kept (``lax.top_k``: of equal scores the
    lower expert number first), renormalised, times the routed scale."""
    r = jax.nn.sigmoid(jnp.matmul(rd(b), rd(wr), precision=HI))
    top, idx = jax.lax.top_k(r, sz["k"])
    return idx, sz["routed_scale"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def routed(b, p, sz, rd=_exact):
    """The routed part of the sparse feed-forward on ``b`` (t, d) that the
    HELD experts add: a plain loop over them, every held expert applied to
    every token and kept at the weight the router gave it there (zero where
    the token did not choose it)."""
    idx, wts = route(b, p["wr"], sz, rd)

    def one(out, expert):
        ex, e1, e3, e2 = expert
        w = jnp.sum(jnp.where(idx == ex, wts, 0.0), axis=-1)
        return out + w[:, None] * gated_ffn(b, e1, e3, e2, rd), None

    numbers = sz["first_expert"] + jnp.arange(sz["experts"])
    return jax.lax.scan(one, jnp.zeros_like(b),
                        (numbers, p["e1"], p["e3"], p["e2"]))[0]


def moe(b, p, sz, rd=_exact):
    return gated_ffn(b, p["s1"], p["s3"], p["s2"], rd) + routed(b, p, sz, rd)


def _layer(x, p, sz, layer, rounding):
    rd, eps = ROUNDINGS[rounding], sz["eps"]
    h = x + rms_norm(attention(rms_norm(x, p["g1"], eps), p, sz, layer, rd),
                     p["g2"], eps)
    b = rms_norm(h, p["g3"], eps)
    f = (moe(b, p, sz, rd) if sz["layers"][layer]["mlp"] == "sparse"
         else gated_ffn(b, p["w1"], p["w3"], p["w2"], rd))
    return h + rms_norm(f, p["g4"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(x, p, sz_key, like, rounding):
    return _layer(x, p, json.loads(sz_key), like, rounding)


def layer_forward(xs, p, sz, layer, rounding="float32"):
    """One layer over a LIST of sequences ``xs`` (each (s_i, d)), a
    sequence at a time.  The first layer of this one's kinds and head count
    stands for it, so that equal layers share one compiled program."""
    sz_key = json.dumps(sz, sort_keys=True)
    like = next(i for i, spec in enumerate(sz["layers"])
                if spec == sz["layers"][layer])
    return [_layer_jit(x, p, sz_key, like, rounding) for x in xs]


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


PAD_TO = 1024   # positions a compared sequence is padded to a multiple of


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
    # padded so that few shapes compile (a causal model's earlier
    # positions do not see the padding)
    padded = [np.pad(f, (0, -len(f) % PAD_TO)) for f in fulls]
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
