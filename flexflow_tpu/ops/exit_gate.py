"""ExitGate — which pass of a looped stack a token leaves by.

A stack run ``T`` times with the same parameters (``models/decoder_lm.py``,
``loops=T``) hands this op the normed state ``h_t`` each pass ended in.  One
``Linear(d, 1)`` with a bias reads an exit probability off each,

    lam_t = sigmoid(w . h_t + b)
    p_t   = lam_t * prod_{j<t} (1 - lam_j)   (t < T);   p_T = prod_{j<T} (1 - lam_j)
    exit  = the first t with p_1 + .. + p_t >= threshold   (T if none does)

and the op's output is ``h_exit``, which goes to the ONE head.  Every pass
has been run by the time the op chooses (as the published forward does: it
runs them all and then takes one), so a step still yields one token a
sequence whatever the threshold; an exit that SAVES the passes not run is the
scheduler's work, not this op's (ROADMAP).  The gate is computed in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..initializers import GlorotUniform, ZeroInitializer
from ..op import Op, OpContext, OpType
from .common import add_wide, cast_compute, read_wide

# the exit mass ``p_t`` is counted in units of 2 ** -MASS_BITS, so that it
# rides the same wide int32 counters as the rest (a step of 8 192 live rows
# stays under ``add_wide``'s 2 ** 30)
MASS_BITS = 16


class ExitGate(Op):
    op_type = OpType.EXIT_GATE
    position_wise = True

    def __init__(self, name, states, threshold: float,
                 kernel_initializer=None):
        super().__init__(name, list(states))
        assert len(states) >= 1 and len({t.shape for t in states}) == 1, (
            [t.shape for t in states])
        self.passes = len(states)
        self.threshold = float(threshold)
        d = states[0].shape[-1]
        self._add_output(states[0].shape, states[0].dtype)
        self.w_kernel = self._add_weight(
            (1, d), kernel_initializer or GlorotUniform(), "kernel")
        self.w_bias = self._add_weight((1,), ZeroInitializer(), "bias")

    def exit_distribution(self, params, states):
        """``(p (.., T) f32, exit (..,) int32 in 0..T-1)`` of the passes'
        states ``(.., d)`` each."""
        w = params[self.w_kernel.name].astype(jnp.float32)[0]
        b = params[self.w_bias.name].astype(jnp.float32)[0]
        lam = jax.nn.sigmoid(jnp.stack(
            [jnp.sum(h.astype(jnp.float32) * w, axis=-1) + b
             for h in states], axis=-1))
        stay = jnp.cumprod(1.0 - lam, axis=-1)      # prod_{j<=t} (1 - lam_j)
        before = jnp.concatenate(
            [jnp.ones_like(stay[..., :1]), stay[..., :-1]], axis=-1)
        p = jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]],
                            axis=-1)
        reached = jnp.cumsum(p, axis=-1)[..., :-1] >= self.threshold
        # the first pass before the last that reaches the threshold; the
        # last one otherwise, whatever rounding made of the sum
        exit_ = jnp.sum(jnp.cumprod((~reached).astype(jnp.int32), axis=-1),
                        axis=-1)
        return p, exit_.astype(jnp.int32)

    def _gate(self, params, inputs, ctx):
        """``(the chosen pass's state, p, which pass each row took as a
        one-hot (.., T) int32)``."""
        p, exit_ = self.exit_distribution(params, inputs)
        took = jax.nn.one_hot(exit_, self.passes, dtype=jnp.int32)
        out = sum(h * took[..., t:t + 1].astype(h.dtype)
                  for t, h in enumerate(inputs))
        return cast_compute(out, ctx), p, took

    def forward(self, params, inputs, ctx: OpContext):
        return [self._gate(params, inputs, ctx)[0]]

    # --- serving ---------------------------------------------------------
    def serve_state(self, slots, num_pages, page_size, mesh_sizes):
        """What the gate counts on the device of the LIVE rows it served
        (prompt chunks, token steps, windows): ``counts`` rows ``tokens``,
        ``passes`` (those each went through: every one, today), then ``T``
        rows of how many left by each pass and ``T`` of the exit mass
        ``p_t`` summed (in units of ``2 ** -MASS_BITS``); each a ``[high,
        low]`` pair (``common.add_wide``)."""
        rows = 2 + 2 * self.passes
        return {"kind": "counter", "shapes": {"counts": (rows, 2)},
                "entries": {"counts": (None, None)}, "dtype": "i32"}

    def serve_step(self, params, inputs, state, where, ctx: OpContext):
        out, p, took = self._gate(params, inputs, ctx)
        live = where.live(inputs[0].shape[1])
        tokens = jnp.sum(live)
        left = jnp.sum(took * live[..., None], axis=(0, 1))
        mass = jnp.sum(jnp.round(p * (1 << MASS_BITS)).astype(jnp.int32)
                       * live[..., None], axis=(0, 1))
        step = jnp.concatenate([jnp.stack([tokens, tokens * self.passes]),
                                left, mass]).astype(jnp.int32)
        return [out], dict(state, counts=add_wide(state["counts"], step))

    def loop_stats(self, counts) -> dict:
        """The op's counters as fetched -> ``{"tokens", "loop_passes",
        "exits_by_pass", "exit_mass_by_pass"}`` (the mass a mean over the
        tokens)."""
        wide = read_wide(counts)
        tokens, passes = wide[:2]
        T = self.passes
        return {"tokens": tokens, "loop_passes": passes,
                "exits_by_pass": wide[2:2 + T],
                "exit_mass_by_pass": [
                    m / (1 << MASS_BITS) / tokens if tokens else 0.0
                    for m in wide[2 + T:]]}

    def parallel_dims(self):
        nd = self.outputs[0].num_dims
        return (True,) * (nd - 1) + (False,)

    def flops(self):
        return (2 * self.passes + 2) * self.outputs[0].volume
