"""Operations and bytes the Laguna family needs, counted from its shapes.

The benchmark's own count: the program's ``op.flops()`` may change with the
program, this may not.  A multiply-add is two operations.  Counted a token:
the attention projections at each layer's own head count (q, k, v, the
per-head gate, the output), the scores and values (a sliding layer over its
window at most), the dense layer's three products, a sparse layer's router
over ALL experts and the ``k`` routed experts and the shared one it takes,
and the LM head.  Not counted: embedding, RMSNorm, rotary, SiLU, softmax,
the sort of a dispatch, and anything a kernel computes beyond what the
algorithm needs (a grouped product's padding rows).
"""

from __future__ import annotations

# the decode step's attention kernel (the kernel's ``name=``, PR 30)
PAGED_DECODE_KERNELS = r"^paged_decode_attention"
# the grouped products of a sparse layer: ``jax.lax.ragged_dot`` is, on a
# v5e, XLA's own grouped-matmul kernel, named ``ragged-dot*`` in a trace
# (looked at by hand, PR 36; the router, the sort and the combine are
# anonymous fusions there and are NOT in this pattern)
MOE_KERNELS = r"^ragged-dot"
# the token step's program, as the ``XLA Modules`` line names it
DECODE_PROGRAM = "jit_decode("
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _kinds(sz):
    full = sum(l["attention"] != "sliding_attention" for l in sz["layers"])
    return full, len(sz["layers"]) - full


def _per_token(sz):
    """Operations a token needs outside attention's scores and values."""
    d, e, G = sz["d_model"], sz["head_dim"], sz["kv_heads"]
    total = 0
    for layer in sz["layers"]:
        H = layer["heads"]
        total += 2 * d * (H * e + 2 * G * e + H) + 2 * H * e * d
        if layer["mlp"] == "sparse":
            total += 2 * d * sz["experts"] + 2 * 3 * d * (
                sz["k"] * sz["expert_ff"] + sz["shared_ff"])
        else:
            total += 2 * 3 * d * sz["d_ff"]
    return total


def serve_flops(sz, decode_tokens, live_positions, prompt_lens):
    """Forward operations serving needs for ``decode_tokens`` tokens decoded
    over ``live_positions`` cached positions in all, and for the prefill of
    prompts of ``prompt_lens`` tokens.  A decoded token attends over all its
    live positions in a full layer and over the window in a sliding one
    (every context of the cell is at least the window long; a shorter one
    is counted at the window, a little high); a prompt is a causal sequence,
    row ``i`` of which sees ``min(i + 1, window)`` keys in a sliding layer.
    Every served token goes through the LM head."""
    e, W = sz["head_dim"], sz["window"]
    head = 2 * sz["d_model"] * sz["vocab"]
    per_key = {True: 0, False: 0}       # sliding? -> 2 * 2 * H * e, summed
    for layer in sz["layers"]:
        per_key[layer["attention"] == "sliding_attention"] += (
            2 * 2 * layer["heads"] * e)
    decode = (decode_tokens * (_per_token(sz) + head)
              + per_key[False] * live_positions
              + per_key[True] * W * decode_tokens)
    prefill = 0
    for p in prompt_lens:
        seen_full = p * (p + 1) // 2
        w = min(p, W)
        seen_sliding = w * (w + 1) // 2 + (p - w) * W
        prefill += (p * _per_token(sz) + head + per_key[False] * seen_full
                    + per_key[True] * seen_sliding)
    return decode + prefill


def window_decode_kv_bytes(sz, live_positions, decode_tokens, itemsize):
    """Bytes of K and V decode attention has to read: every live position in
    the full layers, the window in the sliding ones (every context of the
    cell is at least the window long), whatever reads them."""
    full, sliding = _kinds(sz)
    row = 2 * sz["kv_heads"] * sz["head_dim"] * itemsize
    return row * (full * live_positions
                  + sliding * sz["window"] * decode_tokens)


def moe_decode_bytes(sz, touched_experts, itemsize):
    """Bytes of routed-expert weights that token steps have to read: each
    expert a step's live tokens chose, once, whatever reads them.
    ``touched_experts`` counts them over the steps and the sparse layers
    (the program's own count, ``moe_expert_steps - moe_untouched`` of its
    ``decode_step`` spans); an expert nobody chose need not be read."""
    return touched_experts * 3 * sz["d_model"] * sz["expert_ff"] * itemsize


def sparse_layers(sz):
    return sum(l["mlp"] == "sparse" for l in sz["layers"])
