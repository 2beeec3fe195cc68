"""Operations the post-norm transformer needs, counted from its shapes.

The benchmark's own count: the program's ``op.flops()`` may change with the
program, this may not.  A multiply-add is two operations.  Counted: the four
attention projections, the two feed-forward products, the attention scores
and values.  Not counted: embeddings, LayerNorm, GELU, softmax, the head
(a 2-class head on one token is 3 kFLOP a sequence), and anything a kernel
recomputes (flash attention recomputes the scores in its backward pass).
"""

from __future__ import annotations

# the attention kernels' names in a v5e trace (looked at by hand, PR 23):
# jvp_jit_flash_attention__ / flash_attention is the forward,
# flash_mha_bwd_dkv_* and flash_mha_bwd_dq_* the backward passes
FLASH_KERNELS = r"^(jvp_jit_flash_attention|flash_mha_|flash_attention)"
# the decode step's attention kernel (the kernel's ``name=``, PR 30)
PAGED_DECODE_KERNELS = r"^paged_decode_attention"
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def forward_flops_per_token(sz, seq):
    """Forward operations for one token of a ``seq``-token sequence.  The
    scores and values are counted in full for the encoder and, for a causal
    model, at the half that the mask leaves."""
    d, f, L = sz["d_model"], sz["d_ff"], sz["layers"]
    proj = 2 * 4 * d * d
    ffn = 2 * 2 * d * f
    attn = 2 * 2 * seq * d
    if sz["causal"]:
        attn //= 2
    return L * (proj + ffn + attn)


def train_flops_per_token(sz, seq):
    """Forward + backward = 3 x forward (each product has two gradient
    products)."""
    return 3 * forward_flops_per_token(sz, seq)


def attention_train_flops(sz, batch, seq):
    """Scores + values, forward and backward, for ``batch`` sequences in all
    layers: two products forward, four backward (dV, dP, dQ, dK)."""
    per_seq_layer = 2 * seq * seq * sz["d_model"]   # one product
    if sz["causal"]:
        per_seq_layer //= 2
    return 6 * per_seq_layer * batch * sz["layers"]


def decode_kv_bytes(sz, live_positions, itemsize):
    """Bytes of K and V that ``live_positions`` cached positions hold in all
    layers: what decode attention has to read, whatever reads it.  A decode
    step's live positions are, summed over the streams it serves, the prompt
    and the tokens before the one it produces."""
    return live_positions * 2 * sz["layers"] * sz["d_model"] * itemsize


def serve_flops(sz, decode_tokens, live_positions, prompt_lens):
    """Forward operations serving needs for ``decode_tokens`` tokens decoded
    over ``live_positions`` cached positions in all, and for the prefill of
    prompts of ``prompt_lens`` tokens.  A decoded token attends over all its
    live positions (no mask halves them); a prompt is a causal sequence.
    Every token that is served goes through the LM head, ``2 d vocab``
    operations, a third again of the layers at GPT-1's vocabulary: counted
    here, once a decoded token and once a prompt."""
    d, f, L = sz["d_model"], sz["d_ff"], sz["layers"]
    dense = L * (2 * 4 * d * d + 2 * 2 * d * f)
    head = 2 * d * sz["vocab"]
    decode = decode_tokens * (dense + head) + L * 2 * 2 * d * live_positions
    prefill = sum(p * forward_flops_per_token(sz, p) + head
                  for p in prompt_lens)
    return decode + prefill
