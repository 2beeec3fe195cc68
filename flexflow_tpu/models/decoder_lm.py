"""Pre-norm decoder language model with a LAYER LIST: each layer says its
attention kind (``"full_attention"``, ``"sliding_attention"`` or
``"latent_attention"``), its number of query heads and whether its
feed-forward is ``"dense"`` or ``"sparse"`` (a mixture of experts beside a
shared one).  Rotary positions,
grouped key/value heads, RMSNorm, SiLU-gated feed-forwards, no biases, an
untied head: the block today's open decoders share, built on ``FFModel``'s
normal calls, so it trains, is priced by the search and is served by the
generation engine like any other graph.

    a = RMSNorm(x);  x = x + Attention_l(a)          (rope, groups, gate,
    b = RMSNorm(x);  x = x + F_l(b)                    window: the op's)
    F dense:  (silu(b W1) * (b W3)) W2
    F sparse: shared(b) + scale * sum_k p_k expert_k(b)      (``ops/moe.py``)

With ``sandwich=True`` each sublayer's OUTPUT is normed too before the
residual add: ``x = x + RMSNorm(Attention_l(a))``, ``x = x + RMSNorm(F_l(b))``.

With ``loops=T`` the layer list is laid ``T`` times and every token goes
through all of them: the ops of passes 2..T read pass 1's parameters
(``FFModel.share_weights``), the final norm follows EVERY pass (one scale)
and its output is both the next pass's input and that pass's state ``h_t``;
``exit_gate=threshold`` then chooses which ``h_t`` goes to the one head
(``ops/exit_gate.py``).  A call site has a name of its own, one running
index over the passes (``attention_<t * L + l>``); a parameter keeps the
name pass 1 gave it (``attention_<l>/wq``).  Served, every call site keeps a
cache of its own: pass ``t`` of layer ``l`` reads and writes region ``t`` of
``attention_<l>``'s leaves, and the serving programs run the passes as ONE
loop over the layer list (``FFModel.loop``, ``GraphDecoder._walk``).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from ..config import FFConfig
from ..model import FFModel
from ..tensor import Tensor


def build_decoder_lm(config: FFConfig, layers: Sequence[Dict],
                     d_model: int, head_dim: int, num_kv_heads: int,
                     d_ff: int, vocab_size: int, seq_len: int,
                     rms_eps: float = 1e-6, window: int = 0,
                     rope: Optional[Dict[str, Dict]] = None,
                     gate: bool = False, moe: Optional[Dict] = None,
                     kernel_initializer=None, sandwich: bool = False,
                     latent: Optional[Dict] = None,
                     qk_norm: Optional[float] = None,
                     sparse: Optional[Dict] = None, loops: int = 1,
                     exit_gate: Optional[float] = None
                     ) -> Tuple[FFModel, Tensor, Tensor]:
    """``layers``: one ``{"attention": kind, "heads": query heads, "mlp":
    "dense" | "sparse"}`` a layer.  ``rope``: ``{kind: rope_parameters
    entry}`` (``ops/attention.rope_inv_freq``); ``window`` applies to the
    ``"sliding_attention"`` layers; ``moe``: ``{"num_experts", "k",
    "d_ff", "shared_d_ff", "routed_scale", "scoring", "held"}`` of the
    sparse layers (dropless; ``scoring`` ``"softmax"`` or ``"sigmoid"``,
    ``held`` the ``(first, count)`` of the experts this chip has, all by
    default); ``latent``: ``{"q_rank", "kv_rank", "nope_dim", "rope_dim",
    "v_dim", "rope_theta"}`` of the ``"latent_attention"`` layers
    (``head_dim`` and ``num_kv_heads`` are the other kinds'); ``sandwich``:
    a norm on each sublayer's output as well (``ln_attn_out_<i>``,
    ``ln_ffn_out_<i>``); ``qk_norm`` (an eps) and ``sparse``
    (``{"index_heads", "index_dim", "topk"}``) are the ``"full_attention"``
    layers': an RMSNorm on every query and key head before the rotation,
    and a learned indexer that chooses the keys a query attends over
    (``MultiHeadAttention``); ``loops``: how many times a token passes
    through the layer list, with the same parameters; ``exit_gate``: the
    cumulative exit mass at which a token takes a pass's state to the head
    (``None``: the last pass's, no gate).  Returns ``(model, tokens,
    logits)``."""
    ff = FFModel(config)
    init = kernel_initializer
    tokens = ff.create_tensor((config.batch_size, seq_len), dtype="int32",
                              name="tokens")
    x = ff.embedding(tokens, vocab_size, d_model, aggr="none",
                     kernel_initializer=init, name="tok_embedding")
    first = len(ff.layers)      # where pass 1's ops begin
    states = []
    for t in range(int(loops)):
        begin = len(ff.layers)
        for i, layer in enumerate(layers, start=t * len(layers)):
            kind = layer["attention"]
            a = ff.rms_norm(x, eps=rms_eps, name=f"ln_attn_{i}")
            if kind == "latent_attention":
                a = ff.latent_attention(
                    a, num_heads=int(layer["heads"]), eps=rms_eps,
                    kernel_initializer=init, name=f"attention_{i}", **latent)
            else:
                a = ff.multihead_attention(
                    a, num_heads=int(layer["heads"]),
                    num_kv_heads=num_kv_heads, head_dim=head_dim, causal=True,
                    bias=False, rope=(rope or {}).get(kind), gate=gate,
                    window=window if kind == "sliding_attention" else 0,
                    qk_norm=qk_norm if kind == "full_attention" else None,
                    sparse=sparse if kind == "full_attention" else None,
                    kernel_initializer=init, name=f"attention_{i}")
            if sandwich:
                a = ff.rms_norm(a, eps=rms_eps, name=f"ln_attn_out_{i}")
            x = ff.add(x, a, name=f"res_attn_{i}")
            b = ff.rms_norm(x, eps=rms_eps, name=f"ln_ffn_{i}")
            if layer["mlp"] == "sparse":
                f = ff.moe(b, moe["num_experts"], moe["d_ff"], k=moe["k"],
                           capacity_factor=None, aux_loss_weight=0.0,
                           kernel_initializer=init, gated=True,
                           shared_d_ff=moe.get("shared_d_ff", 0),
                           routed_scale=moe.get("routed_scale", 1.0),
                           scoring=moe.get("scoring", "softmax"),
                           held=moe.get("held"), name=f"moe_{i}")
            else:
                g = ff.dense(b, d_ff, activation="silu", use_bias=False,
                             kernel_initializer=init, name=f"ffn_gate_{i}")
                u = ff.dense(b, d_ff, use_bias=False, kernel_initializer=init,
                             name=f"ffn_up_{i}")
                f = ff.dense(ff.multiply(g, u, name=f"ffn_act_{i}"), d_model,
                             use_bias=False, kernel_initializer=init,
                             name=f"ffn_down_{i}")
            if sandwich:
                f = ff.rms_norm(f, eps=rms_eps, name=f"ln_ffn_out_{i}")
            x = ff.add(x, f, name=f"res_ffn_{i}")
        x = ff.rms_norm(x, eps=rms_eps,
                        name=f"ln_final_{t}" if t else "ln_final")
        states.append(x)
        # a later pass's ops are pass 1's, op for op: one set of parameters,
        # and one op's leaves for what its call sites keep between tokens
        for op, source in zip(ff.layers[begin:], ff.layers[first:begin]):
            op.loop_source, source.loop_passes = source, int(loops)
            if source.weights:
                ff.share_weights(op, source)
    if int(loops) > 1:
        ff.loop = (first, (len(ff.layers) - first) // int(loops), int(loops))
    if exit_gate is not None:
        x = ff.exit_gate(states, threshold=exit_gate,
                         kernel_initializer=init, name="exit_gate")
    logits = ff.dense(x, vocab_size, use_bias=False, kernel_initializer=init,
                      name="lm_head")
    ff.softmax(logits)
    return ff, tokens, logits
