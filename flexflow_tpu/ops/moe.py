"""Mixture-of-Experts layer: one dispatch, by sort, for training and serving.

Capability BEYOND the reference: FlexFlow's closest analogue to expert
parallelism is DLRM's per-embedding-table device placement
(``examples/cpp/DLRM/dlrm.cc:106,469`` + ``dlrm_strategy_hetero.cc``) — one
table per device, no token routing.  This op routes tokens:

* a router (dense gate) scores every token against EVERY expert in f32:
  ``scoring="softmax"`` over the experts, or ``"sigmoid"`` of each logit
  alone; the ``k`` largest scores kept and renormalised to sum to 1 (times
  ``routed_scale``);
* the ``(token, choice)`` pairs are SORTED by expert, so each expert's
  tokens are one contiguous group of rows, and the experts run as two
  GROUPED products over the experts held — static shapes, ``tokens * k``
  rows whatever the routing, nothing dropped, and no ``(tokens, experts,
  capacity)`` tensor anywhere; the rows are then unsorted and combined
  with their router weights.  WHICH grouped product is chosen at trace
  time from what the code can see (:meth:`MoE._grouped_core`): with few
  rows a group on one TPU — a token step, a prompt chunk — the repo's own
  kernel (``ops/grouped_matmul_kernel.py``, ``"rows"``: row tiles of
  16-128, a group's weights read once, an untouched expert not at all); else
  ``jax.lax.ragged_dot`` (``"library"``: XLA's own grouped matmul, tiled
  512 rows a group, right where a batch gives every expert thousands of
  rows, and the only one a gradient is taken through).  Both are
  ``ragged-dot*`` in a device trace; the op notes which it took in
  ``grouped_product``, per program traced;
* a ``capacity_factor``, where a caller still gives one, truncates each
  expert's group IN THAT SAME PATH: the rows past ``C = ceil(k * T / E *
  capacity_factor)`` of a group keep their place and get weight zero
  (GShard's drop policy, by the sorted order: token-major).  ``None`` is
  dropless, the form that serves;
* experts are ungated (``act(x W_up + b) W_down + b``) or ``gated``
  (``(silu(x W1) * (x W3)) W2``, no biases, ``W1 | W3`` stored as one
  ``(d, 2 f)`` matrix an expert so one product reads ``x`` once), and a
  ``shared_d_ff`` adds one shared expert of that form and width that every
  token takes, ungated by the router;
* the op is told which experts it HOLDS.  Under an ``e`` mesh axis every
  shard routes over all experts, runs this same body on the groups of the
  experts it holds (``shard_map``; expert weights carry
  ``shard_axis="e"``) and the parts are summed.  On ONE chip of an
  expert-parallel deployment, with no mesh to say so, ``held=(first,
  count)`` says it: the router keeps all ``num_experts`` outputs and every
  token its ``k`` choices, the expert weights are ``(count, ..)``, the
  same body runs on the groups of experts ``first .. first + count`` and a
  pair routed elsewhere adds nothing here (the chips that hold the others
  add theirs; no code stands in for them).  ``flops()``, the counters and
  ``stats()["moe"]`` then speak of the experts HELD;
* an op that holds FEWER experts than its router scores (``held=``, or a
  shard under an ``e`` axis) and takes no gradient runs everything after
  the sort on its OWN pairs only, a block of rows at a time under a loop
  (:meth:`MoE._experts`, :meth:`MoE.block_rows`): 16 of 256 experts held
  are about 256 of a 512-token chunk's 4 096 pairs, one block of 512, and
  the other 3 840 are neither gathered, multiplied, masked nor combined.
  Exact and dropless for every routing (all 4 096 here would be eight
  blocks); an op that holds every expert, and any op under a gradient,
  keeps the one pass over all pairs.  The op notes the form in
  ``dispatch``, per program traced, as it notes ``grouped_product``;
* an optional Switch-style load-balancing auxiliary loss
  (``E * sum_e f_e * P_e``) is surfaced through ``ctx.aux_losses`` and added
  to the training objective by the fused step.

**Serving** (``docs/serving.md`` "A layer that serves").  Dropless, a
token's result depends on no other token of its step, so ``forward`` on a
chunk, a token step or a window IS the serving step; an op with a capacity
refuses (``serve_check``).  What it keeps between steps is a COUNTER, not
model state: per expert the live tokens it received, the token steps seen
and the experts those steps left untouched, and for an op that holds a
share the pairs that were its own and the blocks they took, accumulated on
the device; the token step returns a copy beside its tokens
(``GraphDecoder.step_tokens``), which rides the boundary's one fetch, for
``stats()["moe"]`` and the ``decode_step`` spans.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..initializers import GlorotUniform, ZeroInitializer
from ..op import Op, OpContext, OpType
from .common import apply_activation, cast_compute


# entries of a block's one-hot combine, (tokens, rows) float32: 16 MB
_ONE_HOT = 1 << 22


class _PerExpertInit:
    """Stacks a base initializer over per-expert keys, so expert i
    initializes exactly like an unstacked FFN with key_i."""

    def __init__(self, base, num_experts: int):
        self.base, self.num_experts = base, num_experts

    def __call__(self, key, shape, dtype):
        keys = jax.random.split(key, self.num_experts)
        return jnp.stack([self.base(k, shape[1:], dtype) for k in keys])


class MoE(Op):
    """Token-routed expert FFN: (n, s, d) -> (n, s, d).  Expert weights are
    stored ``(experts, in, out)``, the layout the grouped product reads."""

    op_type = OpType.MOE
    # what is outside all three is the dispatch: the sort, the gather of
    # the rows, the weighted combine
    scopes = ("moe_router", "moe_experts", "moe_shared")

    def __init__(self, name, input_tensor, num_experts, d_ff, k=2,
                 capacity_factor=1.25, activation="gelu",
                 aux_loss_weight=1e-2, kernel_initializer=None,
                 gated=False, shared_d_ff=0, routed_scale=1.0,
                 scoring="softmax", held=None):
        super().__init__(name, [input_tensor])
        n, s, d = input_tensor.shape
        self.num_experts = int(num_experts)
        self.d_ff = int(d_ff)
        self.k = min(int(k), self.num_experts)
        self.capacity_factor = (None if not capacity_factor
                                else float(capacity_factor))
        self.activation = activation
        self.aux_loss_weight = float(aux_loss_weight)
        self.gated = bool(gated)
        self.shared_d_ff = int(shared_d_ff)
        self.routed_scale = float(routed_scale)
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"{name}: scoring {scoring!r}: 'softmax' or "
                             f"'sigmoid'")
        self.scoring = scoring
        # the experts whose weights this op has: all, or (first, count)
        self.first, self.held = ((0, self.num_experts) if held is None
                                 else (int(held[0]), int(held[1])))
        if not (0 <= self.first and self.held >= 1
                and self.first + self.held <= self.num_experts):
            raise ValueError(f"{name}: held {held} of {num_experts} experts")
        self._add_output((n, s, d), input_tensor.dtype)
        E = self.held
        base = kernel_initializer or GlorotUniform()
        self.w_gate = self._add_weight((self.num_experts, d), base, "gate")

        # per-expert FFN, expert-stacked on dim 0 and sharded over the 'e'
        # mesh axis (≙ the reference's per-table placement, dlrm.cc:106,469
        # — but with token routing)
        def ew(shape, init, nm):
            p = self._add_weight((E,) + shape, _PerExpertInit(init, E), nm,
                                 sharded_dim=0)
            p.shard_axis = "e"
            return p

        up = d_ff * (2 if self.gated else 1)
        self.w_up = ew((d, up), base, "w_up")
        self.w_dn = ew((d_ff, d), base, "w_down")
        self.w_upb = self.w_dnb = None
        if not self.gated:
            self.w_upb = ew((d_ff,), ZeroInitializer(), "w_up_bias")
            self.w_dnb = ew((d,), ZeroInitializer(), "w_down_bias")
        self.w_sup = self.w_sdn = None
        if self.shared_d_ff:
            self.w_sup = self._add_weight((d, 2 * self.shared_d_ff), base,
                                          "shared_up")
            self.w_sdn = self._add_weight((self.shared_d_ff, d), base,
                                          "shared_down")
        # {program: "rows" or "library"}: the grouped product each traced
        # program got, noted at trace time like
        # MultiHeadAttention.decode_core; a program is ("forward" or a
        # serving step's kind, its tokens)
        self.grouped_product = {}
        # {program: "whole" or {"rows": C, "of": A}}: the form of the
        # dispatch each traced program got (_experts), noted the same way
        self.dispatch = {}

    # a dropless op acts on each position alone (serve_check)
    @property
    def position_wise(self) -> bool:
        return self.capacity_factor is None

    @property
    def capacity(self):
        """Rows an expert's group keeps, or ``None`` (dropless)."""
        if self.capacity_factor is None:
            return None
        n, s, _ = self.inputs[0].shape
        return max(1, math.ceil(self.k * n * s / self.num_experts
                                * self.capacity_factor))

    # ---- the one dispatch ----------------------------------------------
    def _route(self, params, xt):
        """``(top_idx (T, k), gates (T, k) f32, probs (T, E) f32)``."""
        with jax.named_scope("moe_router"):
            gate = params[self.w_gate.name].astype(jnp.float32)
            logits = jnp.einsum("td,ed->te", xt.astype(jnp.float32), gate)
            if self.scoring == "sigmoid":
                probs, tiny = jax.nn.sigmoid(logits), 1e-20
            else:
                probs, tiny = jax.nn.softmax(logits, axis=-1), 1e-9
            top_probs, top_idx = jax.lax.top_k(probs, self.k)
            denom = jnp.sum(top_probs, axis=-1, keepdims=True) + tiny
            return top_idx, top_probs / denom * self.routed_scale, probs

    def _grouped_core(self, xs, w_up, w_dn, ctx: OpContext) -> str:
        """``"rows"`` where both grouped products (operands as they are
        multiplied; of ``xs`` its shape and dtype) can take the repo's own
        kernel and it is the right one (:mod:`grouped_matmul_kernel`, from
        what the code can see: backend, operand dtype, rows a group, lane
        alignment, one device, no gradient), else ``"library"``."""
        from . import grouped_matmul_kernel
        distributed = ctx.mesh is not None and ctx.mesh.is_distributed
        return "rows" if all(
            w.dtype == xs.dtype and grouped_matmul_kernel.supported(
                jax.default_backend(), xs.dtype, xs.shape[0], w.shape[0],
                w.shape[1], w.shape[2], distributed, ctx.training)
            for w in (w_up, w_dn)) else "library"

    @staticmethod
    def block_rows(tokens: int, k: int, held: int, experts: int) -> int:
        """Rows of a block of :meth:`_experts`' walk over an op's OWN pairs,
        from the static shapes: whole row tiles of the grouped kernel
        (``row_tile`` of the ``tokens * k`` pairs; 16 where the kernel
        takes no such shape anyway), as many as come nearest TWICE the
        share the op expects, ``pairs * held / experts``: one at least,
        all the pairs at most, and no more than keep a block's one-hot
        combine, ``(tokens, rows)`` float32, within ``_ONE_HOT`` entries.
        A 512-token chunk's 4 096 pairs on 16 of 256 experts: 256
        expected, blocks of 512; a token step's 256 pairs: 16 expected,
        one tile of 128.  Twice, so that an uneven router still ends in
        one block nearly always while a block costs little more than its
        rows (the chip's table, blocks of 256 / 512 / 1 024: PERF.md
        section 5, PR 45)."""
        from .grouped_matmul_kernel import row_tile
        pairs = tokens * k
        tm = row_tile(pairs) or 16
        tiles = max(1, min(round(2 * pairs * held / experts / tm),
                           _ONE_HOT // (tokens * tm)))
        return min(pairs, tm * tiles)

    def _experts(self, weights, xt, top_idx, gates, tokens: int, first: int,
                 ctx: OpContext, program):
        """``(part, ran)``: the routed experts' part of the output, (T, d)
        f32, from the experts ``first .. first + held`` whose stacked
        weights ``weights`` are (``held`` of ``num_experts``; every pair
        routed elsewhere contributes zero here).  ``tokens``: how many
        tokens share the capacity (the whole batch's, also on a token
        shard).  ``program``: the ``(kind, tokens)`` under which the core
        chosen is noted in ``grouped_product`` and the form in
        ``dispatch``.

        Two forms, by what the code can see.  An op that holds EVERY
        expert, or one a gradient is taken through (a loop with a
        data-dependent trip count has no reverse mode), runs everything
        after the sort once over all ``A = T * k`` pairs (``"whole"``;
        ``ran`` is ``None``).  An op that holds FEWER than the router
        scores has only ``own`` of them, at the front of the rolled order:
        it takes those :meth:`block_rows` at a time, ``ceil(own / C)``
        blocks and none where ``own`` is 0 (``{"rows": C, "of": A}``;
        ``ran`` is ``(own, blocks)``, int32 scalars).  Exact and dropless
        for every routing: an op sent all ``A`` pairs runs ``A / C``
        blocks."""
        w_up, w_dn = weights[:2]
        held = w_up.shape[0]
        E, k = self.num_experts, self.k
        T = xt.shape[0]
        A = T * k
        flat = top_idx.reshape(A)
        order = jnp.argsort(flat, stable=True)          # pairs by expert
        expert = flat[order]
        counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        starts = jnp.cumsum(counts) - counts
        weight = gates.reshape(A)[order]
        if self.capacity_factor is not None:
            cap = max(1, math.ceil(k * tokens / E * self.capacity_factor))
            rank = jnp.arange(A) - starts[expert]
            weight = jnp.where(rank < cap, weight, 0.0)
        if held != E:
            # this shard's groups to the front: its pairs are rows
            # 0 .. own of the rolled order, grouped by expert
            offset = starts[first]
            order = jnp.roll(order, -offset)
            expert = jnp.roll(expert, -offset)
            weight = jnp.roll(weight, -offset)
            counts = jax.lax.dynamic_slice(counts, (first,), (held,))
        weights = (cast_compute(w_up, ctx), cast_compute(w_dn, ctx),
                   *weights[2:])
        rows = functools.partial(self._rows, xt, weights, first, ctx,
                                 program)
        if held == E or ctx.training:
            self.dispatch[program] = "whole"
            token, y = rows(order, expert, weight, counts,
                            jnp.arange(A) < jnp.sum(counts))
            return jnp.zeros((T, xt.shape[1]), jnp.float32).at[token].add(
                y), None
        C = self.block_rows(T, k, held, E)
        self.dispatch[program] = {"rows": C, "of": A}
        own = jnp.sum(counts)
        # a last block may reach past A: its rows are past ``own`` too
        order, expert, weight = (jnp.pad(v, (0, -A % C))
                                 for v in (order, expert, weight))
        ends = jnp.cumsum(counts)
        begins = ends - counts

        def block(i, part):
            lo = i * C
            o, e, w = (jax.lax.dynamic_slice(v, (lo,), (C,))
                       for v in (order, expert, weight))
            # each held group's overlap with rows lo .. lo + C
            sizes = jnp.maximum(jnp.minimum(ends, lo + C)
                                - jnp.maximum(begins, lo), 0)
            token, y = rows(o, e, w, sizes, lo + jnp.arange(C) < own)
            # a row of y to its token's row of the part, as a product
            # with a one-hot: exact (ones and zeros), and on a TPU a
            # fraction of what a scatter-add costs, which goes a
            # destination row at a time (a block of 512 rows of 7 680:
            # 0.07 against 1.37 ms, my chip run, PR 45)
            hot = token[None, :] == jnp.arange(T)[:, None]
            return part + jnp.dot(hot.astype(jnp.float32), y,
                                  precision=jax.lax.Precision.HIGHEST)

        blocks = (own + C - 1) // C
        part = jax.lax.fori_loop(0, blocks, block,
                                 jnp.zeros((T, xt.shape[1]), jnp.float32))
        return part, (own, blocks)

    def _rows(self, xt, weights, first: int, ctx: OpContext, program,
              order, expert, weight, sizes, mine):
        """``(token (R,), y (R, d) f32)``: ``R`` pairs of the sorted order
        through their experts (``weights``: the two products' in the
        compute dtype), weighted, to be added to their tokens' rows.
        ``order`` / ``expert`` / ``weight``: the pairs, grouped by expert in
        groups of ``sizes`` (held,); ``mine`` (R,): the rows that ARE pairs
        of a held expert (the rest compute nothing that is kept)."""
        (w_up, w_dn), (b_up, b_dn) = weights[:2], weights[2:] or (None, None)
        held, R = w_up.shape[0], order.shape[0]
        token = order // self.k
        xs = xt[token]                                           # (R, d)
        core = self._grouped_core(xs, w_up, w_dn, ctx)
        self.grouped_product[program] = core
        if core == "rows":
            from .grouped_matmul_kernel import ragged_dot_rows, visits
            grouped = functools.partial(ragged_dot_rows,
                                        walk=visits(sizes, R))
        else:
            grouped = functools.partial(jax.lax.ragged_dot,
                                        preferred_element_type=jnp.float32)
        with jax.named_scope("moe_experts"):
            h = grouped(xs, w_up, sizes)
            local = jnp.clip(expert - first, 0, held - 1)
            if self.gated:
                f = self.d_ff
                h = jax.nn.silu(h[:, :f]) * h[:, f:]
            else:
                h = apply_activation(
                    h + b_up.astype(h.dtype)[local], self.activation)
            h = jnp.where(mine[:, None], cast_compute(h, ctx), 0)
            y = grouped(h, w_dn, sizes)
            if not self.gated:
                y = y + b_dn.astype(y.dtype)[local]
        return token, jnp.where(mine[:, None], y, 0.0) * weight[:, None]

    def _shared(self, params, xt, ctx: OpContext):
        with jax.named_scope("moe_shared"):
            f = self.shared_d_ff
            h = jnp.einsum("td,df->tf", xt,
                           cast_compute(params[self.w_sup.name], ctx),
                           preferred_element_type=jnp.float32)
            h = cast_compute(jax.nn.silu(h[:, :f]) * h[:, f:], ctx)
            return jnp.einsum("tf,fd->td", h,
                              cast_compute(params[self.w_sdn.name], ctx),
                              preferred_element_type=jnp.float32)

    def _moe(self, params, x, ctx: OpContext, kind: str = "forward"):
        """``(out (n, s, d), top_idx (T, k), probs (T, E), ran)``.
        ``kind``: which program this is traced into (a serving step's, or
        ``"forward"``), for ``grouped_product`` and ``dispatch``.  ``ran``:
        :meth:`_experts`' ``(own, blocks)`` of an op TOLD what it holds,
        else ``None`` (the shards of an ``e`` axis keep theirs)."""
        n, s, d = x.shape
        T, E = n * s, self.num_experts
        program = (kind, T)
        xt = cast_compute(x.reshape(T, d), ctx)
        top_idx, gates, probs = self._route(params, xt)
        names = [self.w_up.name, self.w_dn.name] + (
            [] if self.gated else [self.w_upb.name, self.w_dnb.name])
        weights = tuple(params[nm] for nm in names)
        mesh = ctx.mesh
        shards = mesh.axis_size("e") if mesh is not None else 1
        if shards > 1 and self.held != E:
            raise ValueError(
                f"{self.name}: told to hold experts {self.first}.."
                f"{self.first + self.held} of {E} AND given an 'e' mesh "
                f"axis of {shards}: one or the other says what it holds")
        if shards > 1 and E % shards == 0:
            # every shard: all the router's choices, its own experts
            e_axes = mesh.subaxes("e")
            n_axes = mesh.subaxes("n")
            # a capacity ranks a group over the WHOLE batch: tokens then
            # stay whole on every shard
            t_axes = n_axes if (n_axes and self.capacity_factor is None
                                and T % mesh.axis_size("n") == 0) else None
            held = E // shards

            def body(xt, top_idx, gates, first, *w):
                part, _ = self._experts(w, cast_compute(xt, ctx), top_idx,
                                        gates, T, first[0], ctx, program)
                return jax.lax.psum(part, e_axes)

            rows = PartitionSpec(t_axes, None)
            specs = tuple(PartitionSpec(e_axes, *([None] * (w.ndim - 1)))
                          for w in weights)
            # inside a pipeline stage the 'p' axes are manual already:
            # take the context's mesh and make only ours manual (the
            # tokens cross that inner boundary in f32: XLA's CPU
            # partitioner aborts on a bf16 operand there)
            inside = not jax.sharding.get_abstract_mesh().empty
            where = (dict(axis_names=set(e_axes) | set(t_axes or ()))
                     if inside else dict(mesh=mesh.mesh))
            # a shard's first expert rides in as an e-sharded operand
            # (lax.axis_index does not lower beside auto axes:
            # parallel/pipeline.py)
            firsts = jnp.arange(shards, dtype=jnp.int32) * held
            routed = jax.shard_map(
                body, in_specs=(rows, rows, rows, PartitionSpec(e_axes))
                + specs, out_specs=rows, check_vma=False, **where)(
                    xt.astype(jnp.float32) if inside else xt, top_idx,
                    gates, firsts, *weights)
            ran = None
        else:
            routed, ran = self._experts(weights, xt, top_idx, gates, T,
                                        self.first, ctx, program)
        out = routed
        if self.shared_d_ff:
            out = out + self._shared(params, xt, ctx)
        return cast_compute(out, ctx).reshape(n, s, d), top_idx, probs, ran

    def forward(self, params, inputs, ctx: OpContext):
        out, top_idx, probs, _ = self._moe(params, inputs[0], ctx)
        if ctx.training and self.aux_loss_weight > 0.0:
            # Switch load-balance loss: E * sum_e (token fraction * mean
            # router prob); differentiable through P_e
            E = self.num_experts
            f_e = jnp.mean(jax.nn.one_hot(top_idx[:, 0], E), axis=0)
            p_e = jnp.mean(probs, axis=0)
            ctx.aux_losses[self.name] = (self.aux_loss_weight * E
                                         * jnp.sum(f_e * p_e))
        return [out]

    # ---- serving --------------------------------------------------------
    # what an op that holds a share counts of its dispatch (serve_state)
    _DISPATCHED = ("dispatches", "routed", "own_pairs", "blocks", "past_one")

    def serve_check(self, max_seq):
        if self.capacity_factor is not None:
            raise ValueError(
                f"{self.name} (moe) cuts tokens past a capacity "
                f"(capacity_factor={self.capacity_factor}): a token's "
                f"result would depend on which other tokens share its "
                f"step; build it dropless (capacity_factor=None) to serve")

    def serve_state(self, slots, num_pages, page_size, mesh_sizes):
        """Three counters, on the device, of the experts HELD: ``load``
        (held,), the live tokens each received (prompt chunks and token
        steps); ``token_steps``, the token steps that served anybody; and
        ``untouched``, summed over those steps, the held experts no live
        token of the step chose.  An op told to hold FEWER experts than
        its router scores counts its dispatch too (:meth:`_experts`), over
        every step of any kind, pad rows and idle slots included (they
        are dispatched like the rest): ``routed``, the tokens of those
        ``dispatches``; ``own_pairs``, the (token, choice) pairs that were
        this op's; ``blocks``, the blocks they took; ``past_one``, the
        steps that took more than one."""
        scalars = ("token_steps", "untouched") + (
            () if self.held == self.num_experts else self._DISPATCHED)
        return {"kind": "counter",
                "shapes": {"load": (self.held,), **{n: () for n in scalars}},
                "entries": {"load": (None,), **{n: () for n in scalars}},
                "dtype": "i32"}

    def dispatch_stats(self, counters) -> dict:
        """What ``stats()["moe"]`` says of this op's dispatch, from its
        counters as fetched: ``"dispatch"``, the form each serving program
        was traced with (``{"<kind>:<tokens>": "whole" or {"rows": C,
        "of": A}}``), and for an op that holds a share ``own_share`` (of
        all pairs routed, the op's own), ``blocks_per_step`` and
        ``past_one_block_share`` (the steps that needed a second block)."""
        out = {"dispatch": {f"{kind}:{tokens}": form for (kind, tokens), form
                            in tuple(self.dispatch.items())
                            if kind != "forward"}}
        if "own_pairs" in counters:
            c = {n: int(counters[n]) for n in self._DISPATCHED}
            steps = max(c["dispatches"], 1)
            pairs = max(c["routed"], 1) * self.k
            out.update(own_share=c["own_pairs"] / pairs,
                       blocks_per_step=c["blocks"] / steps,
                       past_one_block_share=c["past_one"] / steps)
        return out

    def _held_index(self, expert):
        """Expert numbers -> indices among the experts held; a choice that
        fell on an expert held elsewhere goes past the end."""
        if self.held == self.num_experts:
            return expert
        local = expert - self.first
        return jnp.where((local >= 0) & (local < self.held), local,
                         self.held)

    def serve_step(self, params, inputs, state, where, ctx: OpContext):
        out, top_idx, _, ran = self._moe(params, inputs[0], ctx, where.kind)
        if state is None:
            return [out], state
        live = where.live(inputs[0].shape[1]).reshape(-1)          # (T,)
        # (out-of-bounds updates are dropped: ``.at[].add``'s default)
        picks = jnp.zeros((self.held,), jnp.int32).at[
            self._held_index(top_idx.reshape(-1))].add(
                jnp.repeat(live.astype(jnp.int32), self.k))
        new = dict(state, load=state["load"] + picks)
        if "own_pairs" in state:
            own, blocks = ran
            step = (1, live.shape[0], own, blocks, (blocks > 1).astype(
                jnp.int32))
            new.update({n: state[n] + v
                        for n, v in zip(self._DISPATCHED, step)})
        if where.kind == "token":
            served = jnp.any(live).astype(jnp.int32)
            new["token_steps"] = state["token_steps"] + served
            new["untouched"] = state["untouched"] + served * jnp.sum(
                (picks == 0).astype(jnp.int32))
        return [out], new

    # ---- SOAP legality & cost model -------------------------------------
    def parallel_dims(self):
        # (n, s, c): DP/SP on tokens; the model dim stays whole (expert
        # parallelism rides the dedicated 'e' axis instead)
        return (True, True, False)

    def flops(self):
        """The router over all experts, the routed experts a token takes
        AMONG THOSE HELD (``k`` of them where all are; ``k * held /
        num_experts`` at the expectation where only some are) and the shared
        one: what the grouped products compute, whatever the routing (a
        capacity only zeroes weights)."""
        n, s, d = self.outputs[0].shape
        T = n * s
        up = self.d_ff * (2 if self.gated else 1)
        router = 2 * T * d * self.num_experts
        experts = (2 * T * self.k * (d * up + self.d_ff * d)
                   * self.held // self.num_experts)
        shared = 2 * T * 3 * d * self.shared_d_ff
        return router + experts + shared

    def internal_io_bytes(self, flash_attention=None):
        """The sorted copy of the tokens (every pair: the sort is over all
        the router's choices) and the hidden rows of the experts held, each
        written and read once in the compute dtype (2 B)."""
        n, s, d = self.outputs[0].shape
        up = self.d_ff * (2 if self.gated else 1)
        return 2 * 2 * n * s * self.k * (
            2 * d + (up + self.d_ff) * self.held // self.num_experts)
