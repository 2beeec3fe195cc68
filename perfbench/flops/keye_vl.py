"""Operations and bytes the Keye-VL-2.0 family's decoder needs, counted from
its shapes.

The benchmark's own count: the program's ``op.flops()`` may change with the
program, this may not.  A multiply-add is two operations.  Counted a token:
the attention projections (q, k, v, the output), the indexer's three
projections, the indexer's score against EVERY position a query may see (``2
x index_heads x index_dim`` a pair: the choosing is paid over the whole
history), the heads' scores and values over ``min(history, topk)`` keys (``2 x
2 x heads x head_dim`` a pair: the core is paid over the chosen set only), the
router over all experts, the ``k`` routed experts a token takes, and the LM
head.  Not counted: embedding, norms, rotary, SiLU, softmax, the ReLU and the
weighted sum of the index heads, the choice of ``topk`` (a sort or a
threshold search is no multiply-add), the sort of a dispatch, lane padding,
and anything a kernel computes beyond what the algorithm needs (a core that
reads every live page and masks is paid for the chosen rows only).
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


def _per_token(sz):
    """Operations a token needs outside the indexer's and the heads' scores."""
    d, e, G = sz["d_model"], sz["head_dim"], sz["kv_heads"]
    Hi, di = sz["index_heads"], sz["index_dim"]
    total = 0
    for layer in sz["layers"]:
        H = layer["heads"]
        total += 2 * d * (H * e + 2 * G * e) + 2 * H * e * d
        total += 2 * d * (Hi * di + di + Hi)
        total += 2 * d * sz["experts"] + 2 * 3 * d * sz["k"] * sz["expert_ff"]
    return total


def _index_per_pair(sz):
    return len(sz["layers"]) * 2 * sz["index_heads"] * sz["index_dim"]


def _core_per_pair(sz):
    return sum(2 * 2 * l["heads"] * sz["head_dim"] for l in sz["layers"])


def chosen_positions(sz, decode_tokens, live_positions):
    """Positions the decoded tokens attend over in all, a layer: ``topk``
    each where every history is past it (the cell's), never more than were
    live."""
    return min(live_positions, decode_tokens * sz["topk"])


def serve_flops(sz, decode_tokens, live_positions, prompt_lens):
    """Forward operations serving needs for ``decode_tokens`` tokens decoded
    over ``live_positions`` cached positions in all, and for the prefill of
    prompts of ``prompt_lens`` tokens (each a causal sequence: row ``i``
    scores ``i + 1`` keys and attends over ``min(i + 1, topk)``).  Every
    served token goes through the LM head."""
    head = 2 * sz["d_model"] * sz["vocab"]
    decode = (decode_tokens * (_per_token(sz) + head)
              + _index_per_pair(sz) * live_positions
              + _core_per_pair(sz) * chosen_positions(sz, decode_tokens,
                                                      live_positions))
    prefill = 0
    for p in prompt_lens:
        t = min(p, sz["topk"])
        attended = t * (t + 1) // 2 + (p - t) * sz["topk"]
        prefill += (p * _per_token(sz) + head
                    + _index_per_pair(sz) * (p * (p + 1) // 2)
                    + _core_per_pair(sz) * attended)
    return decode + prefill


def sparse_decode_bytes(sz, live_positions, decode_tokens, itemsize):
    """Bytes the token steps' attention NEEDS, whatever implements it: the
    indexer's key of every live position (``index_dim`` values, whatever the
    stored width) and the K and V rows of the CHOSEN positions, a layer."""
    layers = len(sz["layers"])
    row = 2 * sz["kv_heads"] * sz["head_dim"] * itemsize
    return layers * (live_positions * sz["index_dim"] * itemsize
                     + chosen_positions(sz, decode_tokens, live_positions)
                     * row)


def moe_decode_bytes(sz, touched_experts, itemsize):
    """Bytes of routed-expert weights that token steps have to read: each
    expert a step's live tokens chose, once, whatever reads them
    (``touched_experts``: the program's own count over steps and layers,
    ``moe_expert_steps - moe_untouched`` of its ``decode_step`` spans)."""
    return touched_experts * 3 * sz["d_model"] * sz["expert_ff"] * itemsize
