"""Multi-head LATENT attention: low-rank queries, and ONE compressed
key/value row a token that every head shares.

    c_q = N(a W_qa; g_q)            q = c_q W_qb  -> heads x [q_nope | q_pe]
    [c_kv | k_pe] = a W_kva         c = N(c_kv; g_kv);  q_pe, k_pe <- RoPE
    [k_nope_h | v_h] = c W_kvb,h    per head h;  k_pe is one row for all heads
    s_h(t, u) = (q_nope_h(t).k_nope_h(u) + q_pe_h(t).k_pe(u)) * scale, u <= t
    out = concat_h(softmax_u(s_h) v_h) W_o

What a token leaves behind is ``(c, k_pe)``: ``kv_rank + rope_dim`` values a
layer whatever the number of heads (576 where per-head keys and values of 128
heads would be 40 960).  The same attention can be computed in two forms that
are equal mathematically and reassociate the products:

* **expanded**: ``k_nope`` and ``v`` are made from ``c`` for every key and the
  heads attend as in any attention.  Its cost is the expansion, ``2 x kv_rank
  x heads x (nope + v)`` operations a key, paid once for ALL the queries of a
  step: right where a step has many queries (training, a prompt chunk);
* **absorbed**: ``W_UK,h`` (the ``k_nope`` columns of ``W_kvb,h``) is moved to
  the query, ``q~_h = q_nope_h W_UK,h^T`` (``kv_rank`` wide), the scores are
  ``q~_h . c + q_pe_h . k_pe`` over the cached rows AS THEY LIE, the values
  are ``c`` itself and ``W_UV,h`` is applied to the ``heads`` results.  Its
  cost is ``heads x (2 kv_rank + rope)`` a key and query instead of ``heads x
  (nope + rope + v)``, with nothing expanded: right where a step has one
  query a slot (a token step, a verify window).

Which is taken is read off the step's kind at trace time, never a flag:
``forward`` (training, ``predict``) expands with the dense core;
``serve_step("chunk")`` expands the slot's history a block of keys at a time
under an online softmax, only the blocks the chunk's last row can see (a
12 800-position table costs what the prompt so far costs): inside
``latent_chunk_kernel.latent_chunk_attention`` where that applies (a TPU, one
device, bfloat16, whole lane tiles: a block's expanded keys, values and scores
stay in VMEM), and under XLA's loop over key blocks
(:meth:`LatentAttention._over_key_blocks`) elsewhere — the CPU, a mesh, an odd
shape — which is also what the tests compare the kernel against; ``"token"``
and ``"window"`` absorb, the token step through
``paged_decode_kernel.paged_latent_attention`` where that applies (a TPU, one
device: the latent pages are read once for scores AND values) and over the
gathered view elsewhere.  ``scripts/latent_chunk_forms.py`` times the chunk
three ways on the chip: the loop expanded, the loop absorbed, the kernel
(PERF.md section 5 has the numbers the choice rests on).

**The cache** is one page-major leaf ``"kv"``, ``(num_pages, page, row)``
with ``row`` = ``kv_rank + rope_dim`` rounded up to whole 128-lane tiles, the
padding zero (576 -> 640: what the TPU's tiled memory holds for a 576-wide
array anyway; the scores' contraction and a page's copy then end on tile
boundaries).  The padding is the program's cost: ``row_values`` says what a
token needs.  Every leaf being page-major, prefix reuse, a rejected window's
rollback and migration work as for any paged attention.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..initializers import ConstantInitializer, GlorotUniform
from ..op import Op, OpContext, OpType
from .attention import NEG_INF, _dense_attention, apply_rope
from .common import cast_compute
from .norm import rms_normalize

_LANES = 128
_KEY_BLOCK = 512    # keys a block of the chunk's expanded history, either core


class LatentAttention(Op):
    """Causal self-attention over ``(n, s, d)`` with latent keys and values
    (the module's docstring).  Weights follow Linear's ``(out, in)``
    layout; ``wq_b`` / ``wkv_b`` rows are head-major, a head's ``[nope |
    rope]`` and ``[nope | v]``."""

    op_type = OpType.ATTENTION
    scopes = ("mla_q", "mla_latent", "mla_absorb", "mla_core", "mla_out")

    def __init__(self, name, x, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, rope_theta=10000.0, eps=1e-6,
                 kernel_initializer=None):
        super().__init__(name, [x])
        n, s, d = x.shape
        self.num_heads = int(num_heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim, self.eps = int(v_dim), float(eps)
        # the rotary part turns whole: pairs (i, i + rope_dim / 2)
        self.rope = {"rope_theta": float(rope_theta)}
        self.scale = 1.0 / math.sqrt(self.nope_dim + self.rope_dim)
        # what a token leaves in the cache, and how wide it is stored
        self.row_values = self.kv_rank + self.rope_dim
        self.row_width = -(-self.row_values // _LANES) * _LANES
        # "paged" or "gathered": the core serve_step("token") got, as last
        # traced (GraphDecoder.decode_attention sums it under "latent")
        self.decode_core = None
        self.decode_kind = "latent"
        # {chunk bucket: "kernel" or "loop"}: the core each traced chunk
        # program got (GraphDecoder.chunk_attention sums it under "latent")
        self.chunk_core = {}
        self._add_output((n, s, d), x.dtype)
        init = kernel_initializer or GlorotUniform()
        H = self.num_heads
        one = ConstantInitializer(1.0)
        self.w_qa = self._add_weight((self.q_rank, d), init, "wq_a")
        self.w_qn = self._add_weight((self.q_rank,), one, "q_norm")
        self.w_qb = self._add_weight(
            (H * (self.nope_dim + self.rope_dim), self.q_rank), init, "wq_b",
            sharded_dim=0)
        self.w_kva = self._add_weight((self.row_values, d), init, "wkv_a")
        self.w_kvn = self._add_weight((self.kv_rank,), one, "kv_norm")
        self.w_kvb = self._add_weight(
            (H * (self.nope_dim + self.v_dim), self.kv_rank), init, "wkv_b",
            sharded_dim=0)
        self.w_o = self._add_weight((d, H * self.v_dim), init, "wo",
                                    sharded_dim=1)

    # ---- the parts every form shares ------------------------------------
    @staticmethod
    def _mm(x, w, ctx, eq="nsi,oi->nso"):
        return cast_compute(jnp.einsum(eq, x, cast_compute(w, ctx),
                                       preferred_element_type=jnp.float32),
                            ctx)

    def _queries(self, params, a, positions, ctx):
        """``(q_nope (n, s, H, nope), q_pe (n, s, H, rope))``, rotated."""
        with jax.named_scope("mla_q"):
            n, s, _ = a.shape
            cq = cast_compute(rms_normalize(self._mm(a, params[self.w_qa.name], ctx),
                                   params[self.w_qn.name], self.eps), ctx)
            q = self._mm(cq, params[self.w_qb.name], ctx).reshape(
                n, s, self.num_heads, self.nope_dim + self.rope_dim)
            return (q[..., :self.nope_dim],
                    apply_rope(q[..., self.nope_dim:], positions, self.rope))

    def _latent(self, params, a, positions, ctx):
        """``(c (n, s, kv_rank), k_pe (n, s, rope))``: what is cached."""
        with jax.named_scope("mla_latent"):
            ckv = self._mm(a, params[self.w_kva.name], ctx)
            c = cast_compute(rms_normalize(ckv[..., :self.kv_rank],
                                  params[self.w_kvn.name], self.eps), ctx)
            k_pe = apply_rope(ckv[..., None, self.kv_rank:], positions,
                              self.rope)[..., 0, :]
            return c, k_pe

    def _kvb(self, params, ctx):
        """``W_kvb`` as (H, nope + v, kv_rank), in the compute dtype."""
        return cast_compute(params[self.w_kvb.name], ctx).reshape(
            self.num_heads, self.nope_dim + self.v_dim, self.kv_rank)

    def _expand(self, params, c, ctx):
        """``(k_nope (.., H, nope), v (.., H, v))`` of latent rows ``c``."""
        kv = jnp.einsum("...c,hec->...he", c, self._kvb(params, ctx),
                        preferred_element_type=jnp.float32)
        kv = cast_compute(kv, ctx)
        return kv[..., :self.nope_dim], kv[..., self.nope_dim:]

    def _out(self, params, o, ctx):
        with jax.named_scope("mla_out"):
            n, s = o.shape[:2]
            return self._mm(cast_compute(o, ctx).reshape(n, s, -1),
                            params[self.w_o.name], ctx)

    # ---- training form ----------------------------------------------------
    def forward(self, params, inputs, ctx: OpContext):
        a = cast_compute(inputs[0], ctx)
        n, s, _ = a.shape
        pos = jnp.arange(s)
        q_nope, q_pe = self._queries(params, a, pos, ctx)
        c, k_pe = self._latent(params, a, pos, ctx)
        with jax.named_scope("mla_absorb"):
            k_nope, v = self._expand(params, c, ctx)
        with jax.named_scope("mla_core"):
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None, :], k_nope.shape[:3] + (self.rope_dim,))],
                axis=-1)
            o = _dense_attention(q, k, v, True, self.scale, 0.0, None)
        return [self._out(params, o, ctx)]

    # ---- serving ------------------------------------------------------------
    def serve_state(self, slots, num_pages, page_size, mesh_sizes):
        """ONE page-major leaf, ``(num_pages, page_size, row_width)``: the
        compressed row and its rotary part side by side, zero-padded to
        whole lane tiles; replicated (every head reads every row, so a
        tensor-parallel shard holds the cache whole).  ``values`` is what a
        token needs of a row, for the accounts that price the model and not
        the layout."""
        return {"kind": "kv",
                "shapes": {"kv": (num_pages, page_size, self.row_width)},
                "entries": {"kv": (None, None, None)},
                "dtype": "compute", "values": {"kv": self.row_values}}

    def _rows(self, c, k_pe):
        """``(.., kv_rank)`` and ``(.., rope)`` -> the stored rows."""
        pad = self.row_width - self.row_values
        row = jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)
        return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])

    def _absorbed_queries(self, params, q_nope, q_pe, ctx):
        """``(n, w, H, row_width)``: ``[q_nope W_UK | q_pe | 0]``, what
        scores contract with a stored row."""
        with jax.named_scope("mla_absorb"):
            w_uk = self._kvb(params, ctx)[:, :self.nope_dim, :]
            # (outputs batch-major and transposed after: XLA's CPU backend
            # has no bf16 product that transposes on the way out)
            qt = cast_compute(jnp.einsum(
                "nwhe,hec->hnwc", q_nope, w_uk,
                preferred_element_type=jnp.float32), ctx)
            return self._rows(jnp.transpose(qt, (1, 2, 0, 3)), q_pe)

    def _values_out(self, params, u, ctx):
        """``u`` (n, w, H, kv_rank), the heads' weighted sums of latent
        rows -> (n, w, H, v): ``W_UV`` applied after the sum."""
        with jax.named_scope("mla_absorb"):
            w_uv = self._kvb(params, ctx)[:, self.nope_dim:, :]
            o = jnp.einsum("nwhc,hvc->hnwv", cast_compute(u, ctx), w_uv,
                           preferred_element_type=jnp.float32)
            return jnp.transpose(o, (1, 2, 0, 3))

    def serve_step(self, params, inputs, state, where, ctx: OpContext):
        """One step against the latent pages (``state``: ``{"kv"}``,
        updated in place under donation): project the positions' queries
        and latent rows, scatter the rows at ``(write page, write row)``
        (sentinel pages dropped, as in ``MultiHeadAttention.serve_step``),
        then the core of the step's kind (the module's docstring)."""
        a = cast_compute(inputs[0], ctx)
        n, w, _ = a.shape
        chunk, token = where.kind == "chunk", where.kind == "token"
        positions = ((where.start + jnp.arange(w))[None] if chunk
                     else where.pos[:, None] + jnp.arange(w)[None, :])
        q_nope, q_pe = self._queries(params, a, positions, ctx)
        c, k_pe = self._latent(params, a, positions, ctx)
        pool = state["kv"]
        page, no_page = pool.shape[1], pool.shape[0]
        with jax.named_scope("mla_latent"):
            if chunk:
                qpos = positions[0]
                wp = jnp.take(where.table, qpos // page, mode="clip")
                wp = jnp.where(jnp.arange(w) < where.length, wp, no_page)
                wr = qpos % page
            else:
                wp, wr = where.write_pages, where.write_rows
            rows = self._rows(c, k_pe)
            rows = rows[0] if chunk else rows[:, 0] if token else rows
            pool = pool.at[wp, wr].set(rows.astype(pool.dtype), mode="drop")
        if chunk:
            o = self._chunk_expanded(params, q_nope, q_pe, pool, where, ctx)
            return [self._out(params, o, ctx)], {"kv": pool}
        q = self._absorbed_queries(params, q_nope, q_pe, ctx)
        if token:
            self.decode_core = self._decode_core(pool, ctx)
        if token and self.decode_core == "paged":
            from .paged_decode_kernel import paged_latent_attention
            with jax.named_scope("mla_core"):
                u = paged_latent_attention(
                    q[:, 0], pool, where.table, where.pos, wp, self.scale,
                    self.kv_rank)[:, None]
        else:
            with jax.named_scope("mla_core"):
                view = jnp.take(pool, where.table, axis=0, mode="clip")
                view = view.reshape(n, -1, self.row_width)        # (n, L, e)
                s = jnp.einsum("nwhe,nle->nhwl", q, view,
                               preferred_element_type=jnp.float32) * self.scale
                kpos = jnp.arange(view.shape[1])[None, None, None, :]
                s = jnp.where(kpos > positions[:, None, :, None], NEG_INF, s)
                p = jax.nn.softmax(s, axis=-1)
                u = jnp.transpose(jnp.einsum(
                    "nhwl,nlc->nhwc", p.astype(view.dtype),
                    view[..., :self.kv_rank],
                    preferred_element_type=jnp.float32), (0, 2, 1, 3))
        o = self._values_out(params, u, ctx)
        return [self._out(params, o, ctx)], {"kv": pool}

    def _chunk_expanded(self, params, q_nope, q_pe, pool, where, ctx):
        """A prompt chunk's attention over the slot's pages, EXPANDED: each
        block of cached rows becomes per-head keys and values, inside
        ``latent_chunk_kernel.latent_chunk_attention`` where
        :meth:`_chunk_core` says ``"kernel"`` (the blocks' transients stay
        in VMEM), else under :meth:`_over_key_blocks`' loop; which one this
        program got is noted in ``self.chunk_core``.  -> (1, B, H, v)
        f32."""
        core = self._chunk_core(q_nope, pool, ctx)
        self.chunk_core[q_nope.shape[1]] = core
        if core == "kernel":
            from .latent_chunk_kernel import latent_chunk_attention
            with jax.named_scope("mla_core"):
                return latent_chunk_attention(
                    q_nope[0], q_pe[0], pool, where.table,
                    self._kvb(params, ctx), where.start, where.length,
                    scale=self.scale, rank=self.kv_rank, keys=_KEY_BLOCK)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)[0]          # (B, H, e)

        def block(rows):
            with jax.named_scope("mla_absorb"):
                k_nope, v = self._expand(params, rows[:, :self.kv_rank], ctx)
            k_pe = rows[:, self.kv_rank:self.row_values]
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, None, :], k_nope.shape[:2] + (self.rope_dim,))],
                axis=-1)
            s = jnp.einsum("qhe,khe->hqk", q, k,
                           preferred_element_type=jnp.float32)
            return s, lambda p: jnp.einsum(
                "hqk,khv->hqv", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)

        return self._over_key_blocks(pool, where, q.shape[0], self.v_dim,
                                     block)

    def _over_key_blocks(self, pool, where, B, width, block):
        """A chunk's ``B`` queries against the slot's pages, ``_KEY_BLOCK``
        keys at a time under an online softmax (f32 statistics, the finite
        ``NEG_INF`` mask on global positions), for the blocks up to the
        chunk's last real row only.  ``block(rows (keys, row_width))`` gives
        the unscaled scores ``(H, B, keys)`` of a block's gathered rows and
        a function of the probabilities giving their ``(H, B, width)``
        weighted values: the FORM is the caller's.  Block 0 holds position
        0, which every row sees, so a block that a row sees nothing of
        weighs an exact 0.  -> (1, B, H, width) f32."""
        page, no_page = pool.shape[1], pool.shape[0]
        H = self.num_heads
        block_pages = max(1, _KEY_BLOCK // page)
        keys = block_pages * page
        pps = where.table.shape[0]
        blocks = -(-pps // block_pages)
        table = jnp.pad(where.table, (0, blocks * block_pages - pps),
                        constant_values=no_page)
        qpos = where.start + jnp.arange(B)
        last = where.start + jnp.maximum(where.length, 1) - 1

        def one(b, carry):
            m, l, acc = carry
            pages = jax.lax.dynamic_slice(table, (b * block_pages,),
                                          (block_pages,))
            with jax.named_scope("mla_core"):
                rows = jnp.take(pool, pages, axis=0, mode="clip").reshape(
                    keys, self.row_width)
                s, values = block(rows)
                kpos = b * keys + jnp.arange(keys)
                s = jnp.where(kpos[None, None, :] > qpos[None, :, None],
                              NEG_INF, s * self.scale)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(p, axis=-1)
                acc = alpha[..., None] * acc + values(p)
            return m_new, l, acc

        init = (jnp.full((H, B), NEG_INF, jnp.float32),
                jnp.zeros((H, B), jnp.float32),
                jnp.zeros((H, B, width), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, last // keys + 1, one, init)
        with jax.named_scope("mla_core"):
            return jnp.transpose(acc / l[..., None], (1, 0, 2))[None]

    def _chunk_core(self, q, pool, ctx: OpContext) -> str:
        """``"kernel"`` where a chunk's expanded core can keep a block's
        transients in VMEM (:func:`latent_chunk_kernel.supported`, from
        what the code can see: queries and pool of one dtype), else
        ``"loop"``."""
        from . import latent_chunk_kernel
        distributed = ctx.mesh is not None and ctx.mesh.is_distributed
        return ("kernel" if q.dtype == pool.dtype
                and latent_chunk_kernel.supported(
            jax.default_backend(), pool.dtype, self.kv_rank, self.row_width,
            self.nope_dim, self.v_dim, pool.shape[-2], _KEY_BLOCK,
            distributed, ctx.training) else "loop")

    def _decode_core(self, pool, ctx: OpContext) -> str:
        """``"paged"`` where the token step can read the latent pages in
        place (:func:`paged_decode_kernel.supported`, from what the code can
        see), else ``"gathered"``."""
        from . import paged_decode_kernel
        distributed = ctx.mesh is not None and ctx.mesh.is_distributed
        return ("paged" if paged_decode_kernel.supported(
            jax.default_backend(), pool.dtype, self.num_heads,
            self.row_width, pool.shape[-2], distributed, 1,
            value_lanes=self.kv_rank) else "gathered")

    # ---- SOAP legality & cost model -------------------------------------
    def parallel_dims(self):
        # (n, s, c): samples; the sequence stays whole (no ring form of
        # the latent core is written); heads over c (wq_b, wkv_b, wo)
        return (True, False, True)

    def flops(self):
        """The projections at their own ranks, the expansion of every
        key's latent row, and the expanded core (training form)."""
        n, s, _ = self.outputs[0].shape
        H = self.num_heads
        proj = 2 * n * s * sum(w.volume for w in self.weights
                               if len(w.shape) == 2)
        core = 2 * n * s * s * H * (self.nope_dim + self.rope_dim
                                    + self.v_dim)
        return proj + core

    def internal_io_bytes(self, flash_attention=None):
        """The dense core's scores (f32 written and read, probabilities in
        the compute dtype: 12 B an element, as ``MultiHeadAttention``) and
        the expanded keys and values, written and read once (2 B)."""
        n, s, _ = self.outputs[0].shape
        H = self.num_heads
        return (12 * n * H * s * s
                + 2 * 2 * n * s * H * (self.nope_dim + self.v_dim))
