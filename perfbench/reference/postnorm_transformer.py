"""The plain reference of the post-norm transformer family (BERT-base as a
first-token classifier, GPT-1 as a causal language model).

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``: no kernels, no cache, no batching tricks.  It imports
nothing of the program and takes nothing the program made: the weights come
from :func:`init_params` (one jitted call from the seed, on the device), and
the benchmark hands the same arrays to the program.

Block (Vaswani 2017 / Devlin 2018 / Radford 2018, post-norm):

    a = Attention(x)              softmax(q k^T / sqrt(hd)) v, heads split
    x = LayerNorm(x + a Wo + bo)
    f = GELU(x W1 + b1) W2 + b2
    x = LayerNorm(x + f)

Departures from the published models, which the configuration files repeat
under ``assumed`` (they are what ``flexflow_tpu.models.transformer`` builds):
no q/k/v biases, no token-type embedding, no pooler ``tanh``, GELU in its
``tanh`` form, LayerNorm epsilon 1e-5, the LM head a separate dense with a
bias (not tied to the token embedding), dropout 0.

``rounding`` selects the arithmetic: ``"float32"`` is the reference;
``"float8_e4m3fn"`` is the CONTROL of "How correct is decided": the same
mathematics with both operands of every matrix product (projections, scores,
values, heads) rounded through ``float8_e4m3fn`` under a per-tensor scale, the
nearest precision below the bfloat16 the configurations state, with a
straight-through gradient.  The control has to come out as not correct.

Why the limits in ``perfbench/workloads/*.json`` are what they are is in
PERF.md section 2 ("correct"): each was set from the largest value sound runs
of the program gave over a dozen seeds and the smallest the control gave.
"""

from __future__ import annotations

import functools
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
    r = (x * scale).astype(_F8).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(r - x)


ROUNDINGS = {"float32": _exact, "float8_e4m3fn": _through_f8}


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------
def param_shapes(sz):
    """``{name: shape}``; per-layer leaves are stacked on a leading L."""
    L, d, f = sz["layers"], sz["d_model"], sz["d_ff"]
    out = sz["vocab"] if sz["head"] == "lm" else sz["num_labels"]
    return {
        "tok_emb": (sz["vocab"], d), "pos_emb": (sz["positions"], d),
        "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d), "wo": (L, d, d),
        "bo": (L, d), "ln1_g": (L, d), "ln1_b": (L, d),
        "w1": (L, d, f), "b1": (L, f), "w2": (L, f, d), "b2": (L, d),
        "ln2_g": (L, d), "ln2_b": (L, d),
        "head_w": (d, out), "head_b": (out,),
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shapes, key):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return out


def init_params(sz, seed):
    """Every weight and bias N(0, 0.02) (both models' published
    ``initializer_range``; the biases too, so that a dropped bias shows),
    LayerNorm scales 1.  ``seed`` is any integer: it is folded to 32 bits."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return _init(tuple(sorted(param_shapes(sz).items())), key)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _ln(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, sz, rd):
    n, s, d = x.shape
    h = sz["heads"]
    hd = d // h

    def mm(a, w):
        return jnp.matmul(rd(a), rd(w), precision=HI)

    q = mm(x, p["wq"]).reshape(n, s, h, hd)
    k = mm(x, p["wk"]).reshape(n, s, h, hd)
    v = mm(x, p["wv"]).reshape(n, s, h, hd)
    sc = jnp.einsum("nqhd,nkhd->nhqk", rd(q), rd(k),
                    precision=HI) / math.sqrt(hd)
    if sz["causal"]:
        keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
    a = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", rd(a), rd(v),
                   precision=HI).reshape(n, s, d)
    x = _ln(x + mm(o, p["wo"]) + p["bo"], p["ln1_g"], p["ln1_b"], sz["eps"])
    f = mm(_gelu_tanh(mm(x, p["w1"]) + p["b1"]), p["w2"]) + p["b2"]
    return _ln(x + f, p["ln2_g"], p["ln2_b"], sz["eps"])


_STACKED = ("wq", "wk", "wv", "wo", "bo", "ln1_g", "ln1_b", "w1", "b1",
            "w2", "b2", "ln2_g", "ln2_b")


def hidden(params, tokens, sz, rd=_exact):
    """(n, s) int32 tokens -> (n, s, d) final hidden states."""
    s = tokens.shape[1]
    x = jnp.take(params["tok_emb"], tokens, axis=0) + params["pos_emb"][:s]
    layers = {k: params[k] for k in _STACKED}
    # rematerialised per block so a 512-token micro-batch's float32 score
    # matrices are held for one layer at a time, not twelve
    step = jax.checkpoint(lambda x, p: (_block(x, p, sz, rd), None))
    x, _ = jax.lax.scan(step, x, layers)
    return x


def class_logits(params, tokens, sz, rd=_exact):
    x = hidden(params, tokens, sz, rd)[:, 0]
    return jnp.matmul(rd(x), rd(params["head_w"]),
                      precision=HI) + params["head_b"]


def lm_logits(params, tokens, sz, rd=_exact):
    x = hidden(params, tokens, sz, rd)
    return jnp.matmul(rd(x), rd(params["head_w"]),
                      precision=HI) + params["head_b"]


# --------------------------------------------------------------------------
# training: loss, gradients by jax.grad, Adam written out
# --------------------------------------------------------------------------
def _mean_ce(params, tokens, labels, sz, rd):
    logits = class_logits(params, tokens, sz, rd)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


SAMPLE = 2048      # elements of each leaf (of each layer) that are compared


def sample_positions(shape, stacked):
    """Seeded positions into a leaf (flattened; per layer for a stacked
    leaf): the same for the program's gradient and the reference's."""
    size = int(np.prod(shape[1:] if stacked else shape))
    return np.random.default_rng(20230923).integers(0, size, min(SAMPLE, size))


def leaf_samples(tree):
    """``SAMPLE`` elements of every leaf at :func:`sample_positions`."""
    out = {}
    for k, v in tree.items():
        stacked = k in _STACKED
        idx = sample_positions(v.shape, stacked)
        flat = v.reshape((v.shape[0], -1) if stacked else (-1,))
        out[k] = jnp.take(flat.astype(jnp.float32), idx, axis=-1)
    return out


def leaf_norms(tree):
    """Per-leaf L2 norms; a stacked leaf gives one norm per layer."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if k in _STACKED:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v).reshape(v.shape[0], -1),
                                      axis=1))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


def _sample_norm(samples):
    return jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in samples.values()))


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8), donate_argnums=(0, 1))
def _adam_step(params, mv, t, tokens, labels, sz_items, adam_items, micro,
               rounding):
    sz, adam = dict(sz_items), dict(adam_items)
    rd = ROUNDINGS[rounding]
    n = tokens.shape[0]
    # rows of one label go into one micro-batch (the mean over the batch
    # does not depend on the order): see ``mass`` below
    order = jnp.argsort(labels, stable=True)
    tk = tokens[order].reshape(n // micro, micro, -1)
    lb = labels[order].reshape(n // micro, micro)
    nmb = n // micro

    def body(acc, xs):
        loss, g = jax.value_and_grad(_mean_ce)(params, xs[0], xs[1], sz, rd)
        gsum, norms, sample = acc
        return (jax.tree.map(jnp.add, gsum, g),
                jax.tree.map(jnp.add, norms, leaf_norms(g)),
                sample + _sample_norm(leaf_samples(g))), loss

    zero = jax.tree.map(jnp.zeros_like, params)
    (gsum, norms, sample), losses = jax.lax.scan(
        body, (zero, leaf_norms(zero), jnp.zeros(())), (tk, lb))
    grads = jax.tree.map(lambda g: g / nmb, gsum)
    # the gradient's MASS: the mean over the micro-batches of the norm of
    # each one's gradient.  With mixed labels the batch gradient is what is
    # left of per-row gradients that cancel, by a margin that swings with the
    # seed (its norm is at most the mass, by the triangle inequality, and
    # equals it where all rows pull one way); an arithmetic's error follows
    # the rows' gradients, not what is left of them, so errors are measured
    # against the mass
    mass = (jax.tree.map(lambda x: x / nmb, norms), sample / nmb)
    b1, b2, eps, alpha = (adam["beta1"], adam["beta2"], adam["epsilon"],
                          adam["alpha"])
    t = t + 1
    tf = t.astype(jnp.float32)
    alpha_t = alpha * jnp.sqrt(1.0 - b2 ** tf) / (1.0 - b1 ** tf)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, mv[0], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, mv[1], grads)
    new = jax.tree.map(lambda w, m_, v_: w - alpha_t * m_ / (jnp.sqrt(v_)
                                                            + eps),
                       params, m, v)
    return (new, (m, v), t, jnp.mean(losses), leaf_norms(grads),
            leaf_samples(grads), mass)


@jax.jit
def _delta_norms(new, old):
    return leaf_norms(jax.tree.map(jnp.subtract, new, old))


def train_steps(sz, seed, batches, adam, micro, rounding="float32"):
    """Follow the program's first ``len(batches)`` optimizer steps from the
    seed's weights.  Returns the numbers the comparison reads: the loss of
    every step, the per-leaf norm of the first gradient (as the optimizer
    gets it), a seeded sample of that gradient's elements, the first
    gradient's mass (per leaf, and of the sample; see ``_adam_step``), and
    the per-leaf norm of the parameters' change after the last step.
    Everything comes back as numpy; nothing stays on the device."""
    params = init_params(sz, seed)
    mv = (jax.tree.map(jnp.zeros_like, params),
          jax.tree.map(jnp.zeros_like, params))
    t = jnp.zeros((), jnp.int32)
    sz_items = tuple(sorted(sz.items()))
    adam_items = tuple(sorted(adam.items()))
    losses, grad1, sample1, mass1 = [], None, None, None
    for i, (tokens, labels) in enumerate(batches):
        micro_i = min(micro, tokens.shape[0])
        params, mv, t, loss, gn, gs, mass = _adam_step(
            params, mv, t, jnp.asarray(tokens), jnp.asarray(labels).reshape(-1),
            sz_items, adam_items, micro_i, rounding)
        losses.append(float(loss))
        if i == 0:
            grad1 = jax.tree.map(np.asarray, gn)
            sample1 = jax.tree.map(np.asarray, gs)
            mass1 = jax.tree.map(np.asarray, mass)
    delta = jax.tree.map(np.asarray,
                         _delta_norms(params, init_params(sz, seed)))
    del params, mv
    return {"losses": losses, "grad_norms": grad1, "grad_samples": sample1,
            "grad_mass_norms": mass1[0], "grad_sample_mass": float(mass1[1]),
            "delta_norms": delta}


# --------------------------------------------------------------------------
# serving: how far below the reference's best a token's logit lies
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(3, 4))
def _gap_rows(params, tokens, nxt, sz_items, rounding):
    """For one padded sequence (1, S) and the token that followed each
    position (S,): at every position the reference's best next-token logit,
    the reference's logit of the token that followed, and the reference's
    logit of the token that ``rounding`` arithmetic puts first there."""
    sz = dict(sz_items)
    ref = lm_logits(params, tokens, sz)[0]
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    if rounding == "float32":
        return best, got, best
    low = lm_logits(params, tokens, sz, ROUNDINGS[rounding])[0]
    pick = jnp.argmax(low, axis=-1)
    return best, got, jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]


def served_gaps(sz, seed, requests, rounding="float8_e4m3fn"):
    """``requests`` is a list of ``(prompt, served_tokens)``.  One causal
    forward over prompt + served tokens per request.  Returns, per request,
    the gap ``best - logit[served token]`` at every served position, and the
    same gap for the token the CONTROL arithmetic would have served there."""
    params = init_params(sz, seed)
    sz_items = tuple(sorted(sz.items()))
    S = sz["positions"]
    out = []
    for prompt, served in requests:
        full = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(served, np.int32)])
        padded = np.zeros((1, S), np.int32)
        padded[0, :len(full)] = full
        nxt = np.zeros((S,), np.int32)
        nxt[:len(full) - 1] = full[1:]
        best, got, ctrl = (np.asarray(x, np.float64) for x in _gap_rows(
            params, jnp.asarray(padded), jnp.asarray(nxt), sz_items,
            rounding))
        # position len(prompt) - 1 predicts served[0]
        rows = slice(len(prompt) - 1, len(full) - 1)
        out.append({"served": best[rows] - got[rows],
                    "control": best[rows] - ctrl[rows]})
    del params
    return out
