"""Operations and bytes the openPangu-Ultra-MoE family needs, counted from
its shapes, for ONE CHIP'S SHARE (``sz["experts"]`` of ``sz["router_experts"]``
experts held, the held slice of the vocabulary).

The benchmark's own count: the program's ``op.flops()`` may change with the
program, this may not.  A multiply-add is two operations.  Counted a token:
the latent attention's projections (q_a, q_b, kv_a, the output), the core (in
a prompt, EXPANDED: every key's latent row expanded once a layer, ``2 x
kv_rank x heads x (nope + v)``, then ``2 x heads x (nope + rope + v)`` a
query and key; in a token step, ABSORBED: ``W_UK`` and ``W_UV`` applied to
the one query, ``2 x heads x kv_rank x (nope + v)``, then ``2 x heads x (2
kv_rank + rope)`` a key: 1 088 a head at the published sizes), the dense
layer's three products, a sparse layer's router over ALL ``router_experts``,
the shared expert, and the routed experts a token takes AMONG THOSE HELD:
``k x experts / router_experts`` at the expectation of a router that spreads
its choices evenly (0.5 at 8 of 16 / 256; the program computes the pairs that
really fell on the held experts, which the cell's ``stats()["moe"]`` counts),
and the head over the held slice.  Not counted: embedding, norms, rotary,
SiLU, softmax, the sort of a dispatch, lane padding, and anything a kernel
computes beyond what the algorithm needs.
"""

from __future__ import annotations

# the grouped products of a sparse layer (``ragged-dot*`` in a trace: the
# repo's own kernel and XLA's, flops/laguna.py)
MOE_KERNELS = r"^ragged-dot"
# the token step's program, as the ``XLA Modules`` line names it
DECODE_PROGRAM = "jit_decode("
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def sparse_layers(sz):
    return sum(l["mlp"] == "sparse" for l in sz["layers"])


def _row(sz):
    """Values a token leaves in a layer's cache."""
    return sz["kv_rank"] + sz["rope"]


def _per_token(sz):
    """Operations a token needs outside the attention's core."""
    d, total = sz["d_model"], 0
    for layer in sz["layers"]:
        H = layer["heads"]
        total += 2 * (d * sz["q_rank"]
                      + sz["q_rank"] * H * (sz["nope"] + sz["rope"])
                      + d * _row(sz) + H * sz["v"] * d)
        if layer["mlp"] == "sparse":
            total += 2 * d * sz["router_experts"] + 2 * 3 * d * (
                sz["shared_ff"] + sz["expert_ff"] * sz["k"] * sz["experts"]
                / sz["router_experts"])
        else:
            total += 2 * 3 * d * sz["d_ff"]
    return total


def _decoded_per_key(sz):
    """Operations a decoded token needs a cached position, all layers: the
    absorbed core."""
    return sum(2 * l["heads"] * (2 * sz["kv_rank"] + sz["rope"])
               for l in sz["layers"])


def serve_flops(sz, decode_tokens, live_positions, prompt_lens):
    """Forward operations serving needs for ``decode_tokens`` tokens decoded
    over ``live_positions`` cached positions in all, and for the prefill of
    prompts of ``prompt_lens`` tokens (each a causal sequence: row ``i``
    sees ``i + 1`` keys).  Every served token goes through the held head."""
    head = 2 * sz["d_model"] * sz["vocab"]
    absorb = sum(2 * l["heads"] * sz["kv_rank"] * (sz["nope"] + sz["v"])
                 for l in sz["layers"])
    expand = absorb     # a prompt's key: its row expanded once a layer
    per_pair = sum(2 * l["heads"] * (sz["nope"] + sz["rope"] + sz["v"])
                   for l in sz["layers"])
    decode = (decode_tokens * (_per_token(sz) + absorb + head)
              + _decoded_per_key(sz) * live_positions)
    prefill = sum(p * (_per_token(sz) + expand) + head
                  + per_pair * (p * (p + 1) // 2) for p in prompt_lens)
    return decode + prefill


def latent_decode_need(sz, live_positions, itemsize):
    """``(bytes, operations)`` the token steps' latent core needs for
    ``live_positions`` cached positions read in all: every live position's
    row (``kv_rank + rope`` values, whatever the stored width) once a layer,
    and the absorbed scores and values over it."""
    layers = len(sz["layers"])
    return (live_positions * layers * _row(sz) * itemsize,
            live_positions * _decoded_per_key(sz))


def moe_decode_bytes(sz, touched_experts, itemsize):
    """Bytes of routed-expert weights that token steps have to read: each
    HELD expert a step's live tokens chose, once, whatever reads them.
    ``touched_experts`` counts them over the steps and the sparse layers
    (the program's own count, ``moe_expert_steps - moe_untouched`` of its
    ``decode_step`` spans, which speak of the experts held)."""
    return touched_experts * 3 * sz["d_model"] * sz["expert_ff"] * itemsize
