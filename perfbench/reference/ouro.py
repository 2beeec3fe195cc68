"""The plain reference of the Ouro family (ByteDance, ``model_type`` ``ouro``;
the looped language model of arXiv:2510.25741): a decoder whose WHOLE stack of
layers every token passes through ``T`` times with the same weights, with a
key/value history of its own for every pass, the final norm between passes and
an exit gate that says which pass's state goes to the head.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``: no kernel, no cache, no batching trick.  It imports
nothing of the program and takes nothing the program made.  The weights come
from :func:`leaf`, one leaf at a time from the seed's key, rounded through the
precision the configuration HOLDS them in (``weight_dtype``), so that program
and reference multiply the same numbers; the layer loop makes a layer's leaves
anew in every pass, so that never more than one layer's float32 weights are
alive.

With ``L`` layers and ``T`` passes (``total_ut_steps``), for a token's hidden
vector ``x``::

    x <- E[token]
    for t in 1..T:                                   # the SAME L layers' weights in every pass
        for l in 1..L:
            a = RMSNorm(x; g1_l);  A = Attention_l(a)            # H heads of e, rotary on the whole head, causal,
            x = x + RMSNorm(A; g1o_l)                             #   K/V of THIS pass t: pass t at position i reads
            b = RMSNorm(x; g2_l);  F = W2_l (silu(W1_l b) * (W3_l b))   # what pass t wrote at positions <= i
            x = x + RMSNorm(F; g2o_l)                             # sandwich norms
        h_t = RMSNorm(x; g_final);  x <- h_t                      # the NORMED state is what the next pass starts from
        lam_t = sigmoid(w_gate . h_t + b_gate)                    # the exit gate, one Linear(d, 1)
    p_t = lam_t * prod_{j<t}(1 - lam_j)  (t < T);   p_T = prod_{j<T}(1 - lam_j)
    exit = the first t with p_1 + ... + p_t >= early_exit_threshold;    logits = W_head h_exit

(``T`` where no pass before the last reaches the threshold, whatever rounding
made of the sum.)  With the published threshold 1 every token leaves after
pass ``T``, and all ``T`` passes are always run: the published forward runs
them all and then chooses.  Attention: ``q = a Wq``, ``k = a Wk``, ``v = a
Wv`` as ``H`` heads of ``e`` (as many key/value heads as query heads), q and k
rotated over all ``e`` dims at the token's position (theta ``rope_theta``,
half-split pairing ``(i, i + e / 2)``), softmax over the positions ``<= i`` at
scale ``e ** -0.5``, then ``Wo``; no bias anywhere.  A pass's keys and values
are computed from THAT pass's own ``a``, so in a full causal forward "a cache
of its own for every pass" is simply each pass's attention over its own rows.

**Departures from the equations: none.**  **Assumed**, each listed in the
configuration file: the sandwich norms (``g1o``, ``g2o``), the norm between
passes, the gate's form (Linear with bias, sigmoid, the distribution and the
rule of the first pass that reaches the threshold), a cache of its own for
each pass, no bias on any projection, the rotary's form, weights N(0, 0.02),
norm scales 1, the gate's bias 0.

``rounding``: ``"float32"`` is the reference; ``"float8_e4m3fn"`` the CONTROL
(both operands of every matrix product, the attention's two as well, rounded
through ``float8_e4m3fn`` under a per-tensor scale: the nearest precision below
the bfloat16 the configuration states), which has to come out as not correct;
``"bfloat16"`` is the precision the configuration COMPUTES in, and no control:
the operands of every product AND what every sublayer hands on (each norm's
output, each branch, each residual sum, as a program that computes in bfloat16
stores them) rounded through bfloat16, for ``scripts/ouro_bf16_reading.py``:
what that arithmetic alone does to the served tokens through 192 layer
applications, no program involved.
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


ROUNDINGS = {"float32": _exact, "float8_e4m3fn": _through_f8,
             "bfloat16": _through_bf16}
# what a sublayer hands on is rounded too where the arithmetic is the one the
# configuration computes in (the controls round the products' operands only)
STREAM = {"bfloat16": _through_bf16}


# --------------------------------------------------------------------------
# weights from the seed, a leaf at a time
# --------------------------------------------------------------------------
TOP = ("tok_emb", "g_final", "head", "w_gate", "b_gate")
LAYER = ("g1", "wq", "wk", "wv", "wo", "g1o", "g2", "w1", "w3", "w2", "g2o")
NAMES = TOP + LAYER


def leaf_shape(sz, name, layer=None):
    d, e, G, V, f = (sz["d_model"], sz["head_dim"], sz["kv_heads"],
                     sz["vocab"], sz["d_ff"])
    H = sz["layers"][layer]["heads"] if layer is not None else 0
    return {"tok_emb": (V, d), "g_final": (d,), "head": (d, V),
            "w_gate": (d, 1), "b_gate": (1,),
            "g1": (d,), "g1o": (d,), "g2": (d,), "g2o": (d,),
            "wq": (d, H * e), "wk": (d, G * e), "wv": (d, G * e),
            "wo": (H * e, d), "w1": (d, f), "w3": (d, f), "w2": (f, d)}[name]


def base_key(seed):
    """The key every leaf's key is folded from; ``seed`` is any integer,
    folded to 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf(sz, key, name, layer=None):
    """One leaf from :func:`base_key`'s key, float32: N(0, 0.02) rounded
    through the configuration's ``weight_dtype``; norm scales 1, the gate's
    bias 0.  A layer's leaves are the same in every pass: the key knows the
    layer, never the pass."""
    shape = leaf_shape(sz, name, layer)
    if name.startswith("g"):
        return jnp.ones(shape, jnp.float32)
    if name == "b_gate":
        return jnp.zeros(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.fold_in(key, NAMES.index(name)),
                             0 if layer is None else layer + 1)
    return _draw(key, shape, sz["weight_dtype"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, weight_dtype):
    # drawn flat: the TPU's compiler is far quicker over a row than over
    # the same elements in more dimensions (reference/laguna.py)
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
        return {n: self.leaf(n, layer) for n in LAYER}


def init_params(sz, seed):
    return Params(sz, seed)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def rope(x, positions, theta):
    """``x`` (s, h, e), all ``e`` dims at ``positions`` (s,), pairs ``(i, i +
    e / 2)``."""
    e = x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, e, 2, dtype=np.float64) / e))
    ang = jnp.asarray(positions, jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(a, p, sz, layer, rd=_exact):
    """The normed input ``a`` (s, d) -> the attention branch (s, d): causal
    over the sequence's own rows of this pass."""
    s = a.shape[0]
    H, G, e = sz["layers"][layer]["heads"], sz["kv_heads"], sz["head_dim"]
    pos = np.arange(s)

    def mm(x, w):
        return jnp.matmul(rd(x), rd(w), precision=HI)

    q = rope(mm(a, p["wq"]).reshape(s, H, e), pos, sz["rope_theta"])
    k = rope(mm(a, p["wk"]).reshape(s, G, e), pos, sz["rope_theta"])
    v = mm(a, p["wv"]).reshape(s, G, e)
    q = q.reshape(s, G, H // G, e)
    sc = jnp.einsum("qgre,kge->grqk", rd(q), rd(k),
                    precision=HI) / math.sqrt(e)
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o = jnp.einsum("grqk,kge->qgre", rd(jax.nn.softmax(sc, axis=-1)), rd(v),
                   precision=HI)
    return mm(o.reshape(s, H * e), p["wo"])


def gated_ffn(b, w1, w3, w2, rd=_exact):
    def mm(x, w):
        return jnp.matmul(rd(x), rd(w), precision=HI)
    return mm(jax.nn.silu(mm(b, w1)) * mm(b, w3), w2)


def layer_step(x, p, sz, layer, rd=_exact, st=_exact):
    """One layer on one sequence ``x`` (s, d), sandwich norms; ``st`` rounds
    what each sublayer hands on (:data:`STREAM`)."""
    eps = sz["eps"]
    a = st(attention(st(rms_norm(x, p["g1"], eps)), p, sz, layer, rd))
    x = st(x + st(rms_norm(a, p["g1o"], eps)))
    f = st(gated_ffn(st(rms_norm(x, p["g2"], eps)), p["w1"], p["w3"],
                     p["w2"], rd))
    return st(x + st(rms_norm(f, p["g2o"], eps)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(x, p, sz_key, rounding):
    return layer_step(x, p, json.loads(sz_key), 0, ROUNDINGS[rounding],
                      STREAM.get(rounding, _exact))


def exit_distribution(states, w_gate, b_gate, threshold):
    """``(p (s, T), exit (s,) in 0..T-1)`` of the passes' normed states
    ``states`` (each (s, d)).  The gate is one float32 dot a state: no
    rounding applies to it."""
    lam = jax.nn.sigmoid(jnp.stack(
        [jnp.matmul(h, w_gate, precision=HI)[:, 0] + b_gate[0]
         for h in states], axis=-1))
    T = lam.shape[-1]
    p, stay = [], jnp.ones_like(lam[:, 0])
    for t in range(T - 1):
        p.append(lam[:, t] * stay)
        stay = stay * (1.0 - lam[:, t])
    p = jnp.stack(p + [stay], axis=-1)
    cum = jnp.cumsum(p, axis=-1)
    exit_ = jnp.full(lam.shape[:1], T - 1, jnp.int32)
    for t in reversed(range(T - 1)):
        exit_ = jnp.where(cum[:, t] >= threshold, t, exit_)
    return p, exit_


def passes(params, sequences, sz, rounding="float32"):
    """Token sequences (each (s_i,) int) -> per sequence the list of its
    ``T`` normed states ``h_t``, a layer at a time: one layer's weights are
    made, used and dropped, in every pass again."""
    sz_key = json.dumps(sz, sort_keys=True)
    assert all(l == sz["layers"][0] for l in sz["layers"])
    emb = params.leaf("tok_emb")
    xs = [jnp.take(emb, jnp.asarray(t, jnp.int32), axis=0)
          for t in sequences]
    del emb
    g = params.leaf("g_final")
    states = [[] for _ in xs]
    for _ in range(sz["passes"]):
        for layer in range(len(sz["layers"])):
            p = params.layer(layer)
            xs = [_layer_jit(x, p, sz_key, rounding) for x in xs]
            del p
        st = STREAM.get(rounding, _exact)
        xs = [st(rms_norm(x, g, sz["eps"])) for x in xs]
        for kept, x in zip(states, xs):
            kept.append(x)
    return states


def exits(params, states, sz):
    """``(p (s, T), exit (s,))`` of one sequence's states."""
    return exit_distribution(states, params.leaf("w_gate"),
                             params.leaf("b_gate"), sz["exit_threshold"])


def hidden(params, sequences, sz, rounding="float32"):
    """Token sequences -> the state each position takes to the head: the
    pass's the gate chose."""
    out = []
    for states in passes(params, sequences, sz, rounding):
        _, exit_ = exits(params, states, sz)
        took = jax.nn.one_hot(exit_, sz["passes"], dtype=jnp.float32)
        out.append(sum(h * took[:, t:t + 1] for t, h in enumerate(states)))
    return out


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
    """For one sequence's states at the head (exact, and in ``rounding``
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
    forward (all ``T`` passes) over prompt + served tokens per request, all
    requests through a layer before the next layer's weights are made.
    Returns, per request, the gap ``best - logit[served token]`` at every
    served position, and the same gap for the token the CONTROL arithmetic
    would have served there (``rounding``; with ``"float32"`` the control's
    pass is skipped and its gaps read 0)."""
    params = init_params(sz, seed)
    fulls = [np.concatenate([np.asarray(p, np.int32),
                             np.asarray(t, np.int32)]) for p, t in requests]
    # padded so that few shapes compile (a causal model's earlier positions
    # do not see the padding)
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
