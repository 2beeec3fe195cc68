"""LSTM — the NMT RNN engine's core op (reference ``nmt/lstm.cu:323-503``,
cuDNN fused RNN ``cudnnRNNForwardTraining``/``BackwardData``/``BackwardWeights``).

TPU-native design: cuDNN's fused RNN has no XLA twin, so the cell is built
from primitives the MXU likes —

* the input projection ``x @ Wx`` for ALL timesteps is hoisted out of the
  recurrence into one large (n*s, 4H) matmul (sequence-parallel, shardable
  over the ``s`` axis);
* only the recurrent ``h @ Wh`` matmul + elementwise gate math live inside
  a ``lax.scan`` over time, with cell state carried in float32;
* gate order is i,f,g,o (cuDNN convention); a +1.0 forget-gate bias is the
  standard stability default.

Weight sharing across timesteps (the reference's ``SharedVariable``,
nmt/rnn.h:27-158) is automatic: one parameter read by every scan step, and
its gradient is the sum over timesteps — the two-phase hierarchical replica
reduction (nmt/rnn.cu:650-706) collapses into the scan-transpose plus GSPMD's
psum.  The reference's timestep *chunking* across GPUs
(LSTM_PER_NODE_LENGTH=10, nmt/rnn.h:23) was a latency pipeline for
single-GPU-memory limits; on TPU the whole recurrence stays on-chip and
scaling comes from DP over ``n`` and TP over the gate/hidden dim (``c``),
while the hoisted input projection shards over ``s``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..initializers import GlorotUniform, ZeroInitializer
from ..op import Op, OpContext, OpType
from .common import cast_compute


class LSTM(Op):
    """Single-layer LSTM.  Outputs ``[seq (n,s,H), h_n (n,H), c_n (n,H)]``;
    pass ``initial_state=(h0, c0)`` tensors to chain encoder → decoder."""

    op_type = OpType.LSTM

    def __init__(self, name, input_tensor, hidden_size, initial_state=None,
                 forget_bias=1.0, kernel_initializer=None):
        inputs = [input_tensor]
        if initial_state is not None:
            inputs += [initial_state[0], initial_state[1]]
        super().__init__(name, inputs)
        n, s, d = input_tensor.shape
        self.hidden_size = int(hidden_size)
        self.forget_bias = float(forget_bias)
        self._has_state = initial_state is not None
        h = self.hidden_size
        self._add_output((n, s, h), input_tensor.dtype, idx=0)
        self._add_output((n, h), input_tensor.dtype, idx=1)
        self._add_output((n, h), input_tensor.dtype, idx=2)
        init = kernel_initializer or GlorotUniform()
        # (out, in) layout matching Linear; 4H out = i,f,g,o gate blocks
        self.w_x = self._add_weight((4 * h, d), init, "wx", sharded_dim=0)
        self.w_h = self._add_weight((4 * h, h), init, "wh", sharded_dim=0)
        self.w_b = self._add_weight((4 * h,), ZeroInitializer(), "bias")

    def _weights(self, params, ctx):
        """The (wx, wh_t, bias) triple in the dtypes every execution
        path shares — forward and both kinds of :meth:`serve_step` (a
        whole prompt, one timestep) must run the SAME gate
        arithmetic or the decode parity contract breaks."""
        wx = cast_compute(params[self.w_x.name], ctx)
        # recurrent weights in the compute dtype: the per-step h @ Wh matmul
        # must ride the MXU at bf16 rate (f32 here costs ~3x on v5e); f32
        # accumulation comes from preferred_element_type below and the cell
        # state stays f32 for numerical stability across timesteps
        wh_t = cast_compute(params[self.w_h.name], ctx).T
        b = params[self.w_b.name].astype(jnp.float32)
        return wx, wh_t, b

    def _cell(self, xg_t, h, c, wh_t, b):
        """One LSTM cell update from the pre-projected input gates
        ``xg_t`` (n, 4H) and f32 carry (h, c) — THE gate math, shared
        verbatim by the scan body and the decode step."""
        gates = xg_t + jnp.matmul(
            h.astype(wh_t.dtype), wh_t,
            preferred_element_type=jnp.float32) + b           # (n,4H)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = (jax.nn.sigmoid(f + self.forget_bias) * c
             + jax.nn.sigmoid(i) * jnp.tanh(g))
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return h, c

    def _initial_carry(self, inputs, n):
        if self._has_state:
            return (inputs[1].astype(jnp.float32),
                    inputs[2].astype(jnp.float32))
        return (jnp.zeros((n, self.hidden_size), jnp.float32),
                jnp.zeros((n, self.hidden_size), jnp.float32))

    def forward(self, params, inputs, ctx: OpContext):
        x = cast_compute(inputs[0], ctx)                      # (n,s,d)
        n = x.shape[0]
        wx, wh_t, b = self._weights(params, ctx)
        # hoisted input projection: one big MXU matmul over all timesteps
        xg = jnp.einsum("nsd,gd->nsg", x, wx,
                        preferred_element_type=jnp.float32)   # (n,s,4H)
        h0, c0 = self._initial_carry(inputs, n)

        def step(carry, xg_t):
            h, c = self._cell(xg_t, carry[0], carry[1], wh_t, b)
            return (h, c), h

        # measured on v5e: unroll>1 regresses (43.6% vs 53.7% MFU at n=256)
        # — the unrolled body spills the f32 carries; keep the plain loop
        (h_n, c_n), hs = jax.lax.scan(step, (h0, c0),
                                      jnp.transpose(xg, (1, 0, 2)))
        seq = cast_compute(jnp.transpose(hs, (1, 0, 2)), ctx)
        return [seq, cast_compute(h_n, ctx), cast_compute(c_n, ctx)]

    # ---- serving (docs/serving.md "Token generation") -------------------
    def serve_state(self, slots, num_pages, page_size, mesh_sizes):
        """The f32 ``(h, c)`` carry, ``(slots, hidden)`` each — the state
        IS the cache, and positional carry cannot page.  Slots shard
        over ``n`` by the decode batch's rule, the hidden dim over ``c``
        where it divides."""
        from ..analysis.kv_memory import slot_shard_degree

        c = (mesh_sizes or {}).get("c", 1)
        entries = ("n" if slot_shard_degree(slots, mesh_sizes) > 1 else None,
                   "c" if (c > 1 and self.hidden_size % c == 0) else None)
        shape = (int(slots), self.hidden_size)
        return {"kind": "state",
                "shapes": {"h": shape, "c": shape},
                "entries": {"h": entries, "c": entries},
                "dtype": "f32"}

    def serve_check(self, max_seq):
        if self._has_state:
            raise ValueError(
                f"{self.name}: LSTM with an external initial_state "
                f"is not decodable (seed states are a prefill "
                f"product, not a graph input)")

    def serve_step(self, params, inputs, state, where, ctx: OpContext):
        """A whole prompt or one timestep of every slot, on the carried
        f32 ``{"h", "c"}`` of ``(slots, H)``; the same :meth:`_cell` math
        as forward either way, so decode continues the exact trajectory.

        ``"chunk"``: forward() from the zero state over the slot's WHOLE
        prompt (a chunk at an offset would need the carry of the one
        before it as a program input: a graph with a ``"state"`` leaf
        prefills whole prompts only, and ``start`` is 0), keeping the
        per-step states, of which the one at ``length - 1`` is written
        to row ``slot`` of the carry.

        ``"token"``: one timestep from the carry, ``inputs[0]`` (slots,
        1, d).  The cell runs inside a LENGTH-2 ``lax.scan`` whose
        second step consumes zeros and is discarded.  Not decoration:
        XLA unrolls a trip-count-1 loop and re-fuses the cell's sigmoid
        chain with different vectorization than the full forward's
        while-loop body (measured ~1 ulp drift on CPU — ``sigmoid(a) +
        sigmoid(b)`` in one fusion is compilation-context-dependent),
        while a trip count >= 2 keeps the loop and compiles the
        IDENTICAL body, so decode matches the full-sequence forward
        bit-for-bit (tests/test_generation.py pins it).  The wasted
        second cell is noise next to the decode step's projections."""
        if where.kind == "window":
            raise ValueError(f"{self.name}: a recurrent carry cannot "
                             f"roll back to an accept point")
        x = cast_compute(inputs[0], ctx)
        wx, wh_t, b = self._weights(params, ctx)
        xg = jnp.einsum("nsd,gd->nsg", x, wx,
                        preferred_element_type=jnp.float32)

        def step(carry, xg_t):
            h, c = self._cell(xg_t, carry[0], carry[1], wh_t, b)
            return (h, c), (h, c)

        if where.kind == "chunk":
            h0, c0 = self._initial_carry(inputs, x.shape[0])
            (h_n, c_n), (hs, cs) = jax.lax.scan(
                step, (h0, c0), jnp.transpose(xg, (1, 0, 2)))
            seq = cast_compute(jnp.transpose(hs, (1, 0, 2)), ctx)
            outs = [seq, cast_compute(h_n, ctx), cast_compute(c_n, ctx)]
            hs, cs = (jnp.transpose(hs, (1, 0, 2)),
                      jnp.transpose(cs, (1, 0, 2)))           # (1,s,H)
            h_sel = jax.lax.dynamic_index_in_dim(
                hs, where.length - 1, axis=1, keepdims=False)
            c_sel = jax.lax.dynamic_index_in_dim(
                cs, where.length - 1, axis=1, keepdims=False)
            return outs, {
                "h": jax.lax.dynamic_update_slice(
                    state["h"], h_sel, (where.slot, 0)),
                "c": jax.lax.dynamic_update_slice(
                    state["c"], c_sel, (where.slot, 0))}
        xg2 = jnp.concatenate([jnp.transpose(xg, (1, 0, 2)),
                               jnp.zeros_like(
                                   jnp.transpose(xg, (1, 0, 2)))], 0)
        _, (hs, cs) = jax.lax.scan(step, (state["h"], state["c"]), xg2)
        h2, c2 = hs[0], cs[0]
        seq = cast_compute(h2, ctx)[:, None, :]
        return ([seq, cast_compute(h2, ctx), cast_compute(c2, ctx)],
                {"h": h2, "c": c2})

    def parallel_dims(self):
        # (n, s, c): DP over samples, TP over the hidden/gate dim; the
        # recurrence is serial in s so the sequence dim never splits
        return (True, False, True)

    def flops(self):
        n, s, h = self.outputs[0].shape
        d = self.inputs[0].shape[-1]
        return 2 * n * s * 4 * h * (d + h)

    def sub_problem(self, part_degrees):
        # batch degree shards every input's leading dim; the hidden-TP (c)
        # degree is timed CONSERVATIVELY at full width (forward's 4-way
        # gate split is tied to hidden_size, so a sharded sub-op can't run
        # in isolation) — same upper-bound treatment as attention
        from ..op import pad_degrees
        dn = pad_degrees(part_degrees, 3)[0]
        in_shapes = []
        for t in self.inputs:
            in_shapes.append(t.sub_shape((dn,) + (1,) * (t.num_dims - 1))
                             if t.shape[0] % max(1, dn) == 0 else t.shape)
        return in_shapes, {w.name: w.shape for w in self.weights}
