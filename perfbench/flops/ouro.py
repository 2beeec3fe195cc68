"""Operations and bytes the Ouro family needs, counted from its shapes.

The benchmark's own count: the program's ``op.flops()`` may change with the
program, this may not.  A multiply-add is two operations.  A token goes
through the ``L`` layers ``T`` times (``passes``), so everything a layer
costs is counted ``T x L`` times a token, and the head ONCE (one state a
token goes to it, whichever pass the gate chose).  Counted a token a call
site: the attention projections (q, k, v, the output), the scores and values
against every position the token may see (``2 x 2 x heads x head_dim`` a
pair; each pass has its own keys and values), the gated feed-forward's three
products.  Not counted: embedding, norms, rotary, SiLU, softmax, the exit
gate's ``T`` dots of ``d_model``, lane padding.
"""

from __future__ import annotations

# the paged decode attention kernel as a device trace names it
PAGED_DECODE_KERNELS = r"^paged_decode_attention"
# the token step's program, as the ``XLA Modules`` line names it
DECODE_PROGRAM = "jit_decode("
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def call_sites(sz):
    """Layer applications a token goes through: ``T x L``."""
    return sz["passes"] * len(sz["layers"])


def layer_params(sz):
    """One layer's parameters: the four attention projections, the three
    feed-forward ones, four norm scales."""
    d, e, G = sz["d_model"], sz["head_dim"], sz["kv_heads"]
    H = sz["layers"][0]["heads"]
    return (d * (H * e + 2 * G * e) + H * e * d + 3 * d * sz["d_ff"]
            + 4 * d)


def param_count(sz):
    """Parameters HELD: the ``L`` layers once, whatever the passes, the
    embedding, the untied head, the final norm and the gate."""
    d = sz["d_model"]
    return (len(sz["layers"]) * layer_params(sz) + 2 * sz["vocab"] * d
            + d + d + 1)


def kv_bytes_per_token(sz, itemsize):
    """Bytes of K and V a token leaves behind: a row of ``kv_heads x
    head_dim`` each at every one of the ``T x L`` call sites."""
    return call_sites(sz) * 2 * sz["kv_heads"] * sz["head_dim"] * itemsize


def _per_token(sz):
    """Operations a token needs outside the scores, all passes."""
    d, e, G = sz["d_model"], sz["head_dim"], sz["kv_heads"]
    H = sz["layers"][0]["heads"]
    layer = (2 * d * (H * e + 2 * G * e) + 2 * H * e * d
             + 2 * 3 * d * sz["d_ff"])
    return call_sites(sz) * layer


def _per_pair(sz):
    """Operations a (query, key) pair needs, all passes."""
    return call_sites(sz) * 2 * 2 * sz["layers"][0]["heads"] * sz["head_dim"]


def serve_flops(sz, decode_tokens, live_positions, prompt_lens):
    """Forward operations serving needs for ``decode_tokens`` tokens decoded
    over ``live_positions`` cached positions in all, and for the prefill of
    prompts of ``prompt_lens`` tokens (each a causal sequence: row ``i``
    attends over ``i + 1`` keys).  Every served token goes through the head
    once."""
    head = 2 * sz["d_model"] * sz["vocab"]
    decode = (decode_tokens * (_per_token(sz) + head)
              + _per_pair(sz) * live_positions)
    prefill = sum(p * _per_token(sz) + head
                  + _per_pair(sz) * (p * (p + 1) // 2) for p in prompt_lens)
    return decode + prefill


def decode_kv_bytes(sz, live_positions, itemsize):
    """Bytes of K and V the decoded tokens' attention has to read: every
    live position's row of ``kv_heads x head_dim`` in K and in V, at every
    one of the ``T x L`` call sites."""
    return live_positions * kv_bytes_per_token(sz, itemsize)


def token_step_bytes(sz, steps, live_positions, itemsize):
    """Bytes ``steps`` token steps MUST read, whatever implements them: the
    ``L`` layers' weights once a PASS (a pass cannot begin before the last
    one ended, since its first layer reads the last one's final state, and
    one pass's weights are 40 times the chip's fast memory, so nothing read
    in one pass is still there for the next), the head once, and every live
    position's K and V at every call site."""
    weights = (sz["passes"] * len(sz["layers"]) * layer_params(sz)
               + sz["d_model"] * sz["vocab"]) * itemsize
    return steps * weights + decode_kv_bytes(sz, live_positions, itemsize)
