"""GraphDecoder — autoregressive execution of an FFModel graph over a
PAGED KV cache.

The training/serving executor runs the graph at full sequence length;
generation needs the same graph one position at a time against state
that scales with *live tokens*, not ``slots x max_seq``.  This module
derives both halves from the layer list itself:

* **prefill chunk** — the forward over a ``(1, bucket)`` padded chunk
  of prompt positions ``start .. start+length-1``, through each op's
  own serving step (``Op.serve_step``, kind ``"chunk"``): position-wise
  ops run their forward unchanged, attention scatters the chunk's K/V
  into the slot's pages and attends over the gathered page table —
  history written by earlier chunks or borrowed from the prefix cache,
  plus the chunk itself, causally masked on global positions — and the
  LSTM scans the whole prompt (whole-prompt chunks only — cell state
  cannot page).  One jitted program per
  power-of-two chunk bucket; a single chunk covering the whole prompt
  IS the monolithic prefill, so ``serve_prefill_chunk=0`` reproduces
  the pre-paging behavior program-for-program.
* **decode** — ONE jitted step for the whole ``slots``-wide decode
  batch: embed the current token per slot, run every layer's
  single-position path, scatter K/V at each slot's
  ``(write_page, write_row)`` (host-computed; the pool's ``no_page``
  sentinel drops inactive/prefilling slots' writes), attend over each
  slot's pages — read in place by the paged decode kernel where the
  attention op can use it, else gathered into a view first
  (:meth:`GraphDecoder.decode_attention` says which) — argmax the next
  token.  The cache pytree is donated, so XLA updates the (potentially
  multi-GB) pools in place.

Pool geometry and sharding come from
:mod:`flexflow_tpu.analysis.kv_memory` — the SAME module the static
FF108/FF121/FF130 memory gates integrate, so what lint predicts is
what this decoder allocates (the arrays themselves come from
``pages.alloc_pool_arrays``, the one allocation site RL013 pins).
The K/V pools are stored lane-dense, ``(num_pages, page_size, heads *
head_dim)``: the attention ops fold new rows before the scatter and
unfold only the gathered view, so no program compiled here copies a
pool (:meth:`GraphDecoder.pool_copies` reads the compiled text and
says so; under ``(.., heads, head_dim)`` the TPU compile of a decode
layer held eight pool-sized copies).  The folded dim shards over the
tensor-parallel ``c`` mesh axis by whole heads; the page dim is
replicated (pages are interchangeable across slots).

Every program is ONE walk of the layer list (:meth:`GraphDecoder.
_walk`) that hands each op its inputs, its declared state and where
the step stands (``op.ServeStep``); what a layer keeps between tokens,
whether it can generate at all and how it advances are the op's own
(``Op.serve_state`` / ``serve_check`` / ``serve_step``), so this module
names no op class.  The walk runs each op under
``jax.named_scope(op.name)`` as ``FFModel``'s forward does, and what a
program does outside any graph op under one of
``obs.device_ops.SERVE_OWNERS``, so a profiler trace of a serving
program can be summed by graph op
(:meth:`GraphDecoder.program_op_tables`).  Supported graphs: one (n, s)
int token input; position-wise ops
(dense/norms/elementwise/softmax/dropout/embedding, a dropless MoE),
causal self-attention (grouped heads, rotary positions, a window with
rows of its own; a learned selection of keys, its indexer's keys a third
page-major leaf; latent attention, one shared row a token in one
page-major leaf), stateless-init LSTM, learned position
embeddings, and whatever else writes the contract.  Anything else
(convs, splits, cross-attention, an MoE with a capacity, pipelines) fails
validation loudly at construction — a generation engine must never
silently produce wrong tokens for an unsupported graph.  What a graph
with a WINDOWED entry cannot do yet is refused in one place,
:meth:`GraphDecoder.refusal`.  A stack the graph lays several times with
the same parameters (``FFModel.loop``) is walked once, under a loop over
its passes, each pass in a region of its own of the ops' leaves
(:meth:`GraphDecoder._walk`).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...analysis.kv_memory import (COUNTERS, DEFAULT_PAGE_SIZE,
                                   default_num_pages, kv_cache_layout,
                                   pages_per_slot)
from ...obs.device_ops import (SERVE_OWNERS, stale_cache_error,
                               table_from_hlo, unnamed_owners)
from ...op import OpContext, ServeStep
from . import sampling
from .pages import alloc_pool_arrays, entry_dtype


# one computation of a compiled module's text: `%name (params) -> type {`
# (or `ENTRY %name ...`) up to the closing brace on a line of its own
_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%(?P<name>[^\s(]+)\s*\(.*?\{\n(?P<body>.*?)^\}",
    re.M | re.S)
# `%copy.3 = bf16[4096,16,768]{...} copy(...)`; an asynchronous copy's
# `copy-start` yields a tuple whose first shape is the copied array's
_COPY = re.compile(r"^\s*(?P<root>ROOT\s+)?%\S+\s*=\s*\(?\s*(?P<dtype>\w+)"
                   r"\[(?P<dims>[\d,]*)\][^=]*?\scopy(?:-start)?\(", re.M)
_FUSION_CALL = re.compile(r"\sfusion\(.*?calls=%(?P<callee>[^\s,)]+)")
# bytes per element by XLA's primitive type names
_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
              "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
              "f64": 8}


def count_copies(hlo_text: str, elements) -> Dict[str, int]:
    """``{"count", "bytes"}`` of the ``copy`` operations in a compiled
    module's text (``compiled.as_text()``) whose result holds one of
    the element counts in ``elements`` — the reading behind
    :meth:`GraphDecoder.pool_copies`.  By element count and not by
    shape, because a transposing copy keeps the elements and changes
    everything else.

    Counted is a copy that is a device operation of its own and writes
    its whole result to memory: a ``copy`` (or ``copy-start``)
    instruction of an unfused computation (the entry, a loop's body),
    or the ROOT of a fusion that an unfused computation calls.  A copy
    nested deeper — a fusion INSIDE a convolution's fusion — is how
    that convolution reads its operand in the order it wants, tile by
    tile, and passes over no memory of its own."""
    elements = frozenset(int(e) for e in elements)
    bodies = {m.group("name"): m.group("body")
              for m in _COMPUTATION.finditer(hlo_text)}
    calls = {name: {c.group("callee") for c in _FUSION_CALL.finditer(body)}
             for name, body in bodies.items()}
    fused = set().union(*calls.values())
    top_fused = set().union(*(callees for name, callees in calls.items()
                              if name not in fused))
    count = nbytes = 0
    for name, body in bodies.items():
        if name in fused and name not in top_fused:
            continue
        for m in _COPY.finditer(body):
            if name in fused and not m.group("root"):
                continue
            vol = 1
            for d in m.group("dims").split(","):
                vol *= int(d) if d else 1
            if vol in elements:
                count += 1
                nbytes += vol * _HLO_BYTES.get(m.group("dtype"), 1)
    return {"count": count, "bytes": nbytes}


def program_name(fn) -> str:
    """The name a profiler trace prints for the jitted ``fn``'s program
    (``jit_decode`` for ``decode``, ``jit_prefill_512`` for the 512-token
    chunk's): THE one rule, for a span's ``program``, a trace's ``XLA
    Modules`` line and :meth:`GraphDecoder.program_op_tables`' keys."""
    return "jit_" + getattr(fn, "__name__", "")


def prefill_buckets(max_seq: int) -> Tuple[int, ...]:
    """Power-of-two chunk buckets 2, 4, ... capped at ``max_seq``
    (always included) — one compiled prefill-chunk program per bucket.
    The floor of 2 is the matrix-vector parity rule (a 1-row program's
    bits drift ~1 ulp, like serve_buckets)."""
    out: List[int] = []
    b = 2
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(int(max_seq))
    return tuple(out)


class GraphDecoder:
    """Prefill-chunk + decode executables for one (model, slots,
    max_seq, page geometry).  Use :meth:`for_model` — instances cache
    their jitted programs, and engines sharing a geometry share the
    compiles."""

    def __init__(self, model, slots: int, max_seq: int,
                 page_size: int = 0, num_pages: int = 0,
                 prefill_chunk: int = 0):
        if slots < 2:
            raise ValueError(
                f"slots must be >= 2, got {slots}: a 1-slot decode "
                f"batch lowers matrix-vector kernels whose bits differ "
                f"from the full forward (same floor as serve_buckets)")
        self.model = model
        self.slots = int(slots)
        self.max_seq = int(max_seq)
        cfg = model.config
        self.page_size = int(page_size
                             or getattr(cfg, "serve_kv_page", 0)
                             or DEFAULT_PAGE_SIZE)
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, "
                             f"got {self.page_size}")
        self.pages_per_slot = pages_per_slot(self.max_seq, self.page_size)
        self.num_pages = int(num_pages
                             or getattr(cfg, "serve_kv_pages", 0)
                             or default_num_pages(self.slots, self.max_seq,
                                                  self.page_size))
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold even one "
                f"max_seq={self.max_seq} stream "
                f"({self.pages_per_slot} pages of {self.page_size})")
        self._validate()
        self.buckets = prefill_buckets(self.max_seq)
        mesh = model.mesh
        self._mesh_sizes = dict(mesh.sizes) if mesh is not None else None
        self.layout = kv_cache_layout(model.layers, self._mesh_sizes,
                                      self.slots, self.max_seq,
                                      page_size=self.page_size,
                                      num_pages=self.num_pages,
                                      prefill_chunk=int(prefill_chunk))
        # entries with rows of their own, sized for chunks up to
        # ``prefill_chunk`` (0 = whole prompts): a longer chunk would
        # overwrite rows its own first query still reads
        self.windowed = {name: ent for name, ent in self.layout.items()
                         if ent.get("window")}
        # what ops count on the device for stats(), by entry name
        self.counters = tuple(name for name, ent in self.layout.items()
                              if ent["kind"] == "counter")
        if self._loop is not None:      # the entries the loop carries
            mine = {op.name for op in model.layers[
                self._loop[0]:self._loop[0] + self._loop[1]]}
            self._loop_state = tuple(
                name for name in self.layout
                if name in mine or name[:-len(COUNTERS)] in mine)
        kinds = {ent["kind"] for ent in self.layout.values()} - {"counter"}
        # a fixed per-slot "state" leaf cannot page: a chunk at offset k
        # would need the carry from chunk k-1 as a program input —
        # whole-prompt chunks only (the engine enforces it)
        self.supports_chunking = "state" not in kinds
        # an engine that prefills in chunks of ``prefill_chunk`` dispatches
        # no longer one, so no program for one is built, warmed or read: at
        # a long ``max_seq`` the buckets past the chunk cost minutes of
        # set-up, and the whole-prompt one need not even fit the device (a
        # 12 800-token chunk of a wide sparse layer did not: PERF.md, PR 41)
        if prefill_chunk and self.supports_chunking:
            self.buckets = prefill_buckets(min(self.max_seq,
                                               int(prefill_chunk)))
        # every leaf page-major: what prefix reuse, speculation's
        # rollback and KV migration each need of the graph's state
        self.pageable = kinds == {"kv"}
        self._prefill_fns: Dict[int, object] = {}
        self._decode_fn = None
        self._decode_sampled_fn = None
        self._verify_fns: Dict[Tuple[int, bool], object] = {}
        self._draft_fns: Dict[Tuple[int, bool], object] = {}
        # what the compiled text of a program said, read once a program
        # and device: (pool-sized copies, owner table, the owners the
        # table leaves out) (_read_programs)
        self._program_reads: Dict[Tuple[str, object], Tuple] = {}

    def refusal(self, what: str):
        """THE gate beside ``pageable``: why this graph's state cannot do
        ``what`` (``"prefix reuse"``, ``"speculation"``, ``"migration"``),
        or ``None`` if it can.  Each needs every leaf page-major in the
        SHARED pool: a reused prefix borrows pages by id, a rejected
        window is rolled back by position, a migrated stream ships its
        page chain.  A windowed entry keeps a ring of its own a slot,
        rewritten in place: it has no page to lend, holds no position
        older than its window to roll back to, and its rows are not in
        the chain.  Named, never silently wrong."""
        if not self.pageable:
            return (f"{what} needs paged attention state throughout "
                    f"(a fixed per-slot state leaf cannot page)")
        if self.windowed:
            name, ent = next(iter(self.windowed.items()))
            return (f"{what} is not supported over a windowed cache entry: "
                    f"{name} keeps the last {ent['window']} positions in "
                    f"{ent['rows']} rows of its own a slot, outside the "
                    f"page pool ({len(self.windowed)} such "
                    f"entr{'y' if len(self.windowed) == 1 else 'ies'})")
        return None

    # ---- validation ----------------------------------------------------
    def _validate(self) -> None:
        model = self.model
        if len(model.input_tensors) != 1:
            raise ValueError(
                f"generation needs exactly one token input, model has "
                f"{len(model.input_tensors)}")
        tin = model.input_tensors[0]
        if len(tin.shape) != 2 or not np.issubdtype(np.dtype(tin.dtype),
                                                    np.integer):
            raise ValueError(
                f"generation input must be (n, s) integer token ids, "
                f"got {tin.shape} {tin.dtype}")
        self._input_uid = tin.uid
        final = getattr(model, "_final_tensor", None) or \
            model.layers[-1].outputs[0]
        if len(final.shape) != 3:
            raise ValueError(
                f"generation needs per-token (n, s, vocab) outputs, "
                f"final tensor is {final.shape} — use an LM graph "
                f"(models.build_transformer_lm / build_lstm_lm), not a "
                f"classifier")
        self._final_uid = final.uid
        self._vocab = int(final.shape[-1])
        for op in model.layers:
            op.serve_check(self.max_seq)
        # a stack laid several times: ``(first op, ops a pass, passes)``,
        # the ONE tensor a pass reads from outside itself, and each pass's
        # last output (what the next starts from, and what reads them all)
        self._loop = model.loop
        if self._loop is not None:
            first, body, passes = self._loop
            ops = model.layers[first:first + passes * body]
            made = {t.uid for op in ops[:body] for t in op.outputs}
            outside = {t.uid for op in ops[:body] for t in op.inputs
                       if t.uid not in made}
            if len(outside) != 1:
                raise ValueError(
                    f"a looped stack must read ONE tensor from outside "
                    f"itself, pass 1 reads {len(outside)}")
            self._loop_io = (outside.pop(), [
                ops[t * body + body - 1].outputs[0].uid
                for t in range(passes)])

    # ---- shared context ------------------------------------------------
    def _ctx(self) -> OpContext:
        cfg = self.model.config
        return OpContext(
            training=False, rng=None, compute_dtype=cfg.compute_dtype,
            mesh=self.model.mesh, flash_attention=cfg.flash_attention,
            conv_layout=getattr(self.model, "resolved_conv_layout",
                                "nchw"))

    # ---- cache ---------------------------------------------------------
    def init_cache(self) -> Dict[str, Dict[str, jax.Array]]:
        """Preallocate the page pools + LSTM state, placed under the
        layout's PartitionSpecs — through ``pages.alloc_pool_arrays``,
        the ONE KV allocation site (RL013; the bytes the
        FF108/FF121/FF130 gates charge are exactly these
        allocations)."""
        return alloc_pool_arrays(self.layout, self.model.mesh,
                                 self.model.config.compute_dtype)

    # ---- prefill -------------------------------------------------------
    def prefill_bucket(self, chunk_len: int) -> int:
        """Smallest chunk bucket covering ``chunk_len``."""
        for b in self.buckets:
            if b >= chunk_len:
                return b
        raise ValueError(f"prefill chunk of {chunk_len} tokens exceeds "
                         f"max_seq {self.max_seq}")

    def prefill_fn(self, bucket: int):
        """The jitted prefill-CHUNK program for one bucket:
        ``fn(params, caches, tokens (1, bucket), table_row
        (pages_per_slot,), slot, start, length) -> (next_token,
        caches)`` — runs the forward over chunk positions ``start ..
        start+length-1``, scatters the chunk's K/V into the slot's
        pages / writes the LSTM carry at ``length - 1``, and argmaxes
        the chunk's last real position's logits.  For the FINAL chunk
        that argmax is the stream's FIRST generated token (TTFT is the
        last chunk's dispatch); intermediate chunks' return value is
        ignored.  The cache pytree is donated."""
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        if bucket not in self.buckets:
            raise ValueError(f"unknown prefill bucket {bucket}")

        def prefill(params, caches, tokens, table_row, slot, start,
                    length):
            logits, new = self._walk(params, caches, tokens, ServeStep(
                "chunk", table_row, start=start, length=length, slot=slot,
                no_page=self.num_pages))
            with jax.named_scope("sample"):
                last = jax.lax.dynamic_index_in_dim(
                    logits, length - 1, axis=1, keepdims=False)[0]
                nxt = jnp.argmax(last).astype(jnp.int32)
            return nxt, new

        # a program a bucket, told apart by name in a trace
        prefill.__name__ = f"prefill_{bucket}"
        fn = jax.jit(prefill, donate_argnums=(1,))
        self._prefill_fns[bucket] = fn
        return fn

    # ---- decode --------------------------------------------------------
    def decode_fn(self):
        """THE decode step, jitted once per geometry:
        ``fn(params, caches, tokens (slots,), pos (slots,), table
        (slots, pages_per_slot), write_pages (slots,), write_rows
        (slots,)) -> (next_tokens (slots,), caches)`` (for a graph that
        counts, ``((next_tokens, counters), caches)``: :meth:`step_tokens`).
        Every slot
        advances one position per call — inactive/prefilling slots
        compute on dummy inputs with ``write_pages`` at the pool's OOB
        sentinel (their scatter drops; a write through a stale table
        entry could corrupt a SHARED prefix page), which keeps the
        program shape static.  Greedy argmax decoding: deterministic,
        and exactly what the replicated ``predict``-style reference
        does — the engine==reference parity pin compares token ids."""
        if self._decode_fn is not None:
            return self._decode_fn

        def decode(params, caches, tokens, pos, table, write_pages,
                   write_rows):
            logits, new = self._walk_decode(params, caches, tokens, pos,
                                            table, write_pages,
                                            write_rows)
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._counted(nxt, new), new

        self._decode_fn = jax.jit(decode, donate_argnums=(1,))
        return self._decode_fn

    def decode_sampled_fn(self):
        """The SAMPLED decode step: the same layer walk as
        :meth:`decode_fn` with the argmax replaced by per-slot
        temperature/top-k/top-p sampling from the request-seeded
        on-device PRNG streams (``sampling.STREAM_MAIN`` folded with
        the GLOBAL position of the token being drawn, so the same
        (seed, request) replays the same tokens).  Slots with
        ``temperature <= 0`` get the exact one-hot argmax distribution
        — but the engine still routes ALL-greedy batches through
        :meth:`decode_fn`, so the unsampled bit-parity pins never
        depend on this program.
        ``fn(params, caches, tokens, pos, table, write_pages,
        write_rows, temp (slots,), top_k (slots,), top_p (slots,),
        seeds (slots,)) -> (next_tokens, caches)``."""
        if self._decode_sampled_fn is not None:
            return self._decode_sampled_fn

        def decode_s(params, caches, tokens, pos, table, write_pages,
                     write_rows, temp, top_k, top_p, seeds):
            logits, new = self._walk_decode(params, caches, tokens, pos,
                                            table, write_pages,
                                            write_rows)
            with jax.named_scope("sample"):
                probs = sampling.filtered_probs(logits, temp, top_k, top_p)
                keys = sampling.position_keys(sampling.request_keys(seeds),
                                              pos + 1, sampling.STREAM_MAIN)
                nxt = sampling.categorical(keys, probs)
            return self._counted(nxt, new), new

        self._decode_sampled_fn = jax.jit(decode_s, donate_argnums=(1,))
        return self._decode_sampled_fn

    # ---- the graph walk -------------------------------------------------
    def _walk(self, params, caches, x, where: ServeStep):
        """THE graph walk, which every program runs: ``x`` — a (1,
        bucket) prompt chunk, (slots, 1) current tokens or a (slots, W)
        window, as ``where`` says — through every op's ``serve_step``
        with the op's own leaves of ``caches``.  Returns the final
        tensor's value (.., V) and the caches as the ops left them: the
        same structure and leaf names, since the programs donate them
        and hand them on.

        A stack the graph lays several times with the same parameters
        (``model.loop``) is walked ONCE, under a loop over its passes:
        the ops of pass 1 run each time (they ARE the later passes' ops,
        parameter for parameter), pass ``t`` reading and writing region
        ``t`` of their leaves (:meth:`_in_pass`), so a program holds one
        pass's instructions whatever the number of passes (walked op by
        op, the 192 call sites of a 48-layer stack run four times took
        the TPU's compiler 46-57 s a program, nine programs an engine:
        PERF.md, PR 49)."""
        ctx = self._ctx()
        values: Dict[int, jax.Array] = {self._input_uid: x}
        new: Dict[str, Dict[str, jax.Array]] = {}
        ops = self.model.layers
        if self._loop is None:
            self._run(ops, params, caches, new, values, where, ctx)
            return values[self._final_uid], new
        first, body, passes = self._loop
        self._run(ops[:first], params, caches, new, values, where, ctx)
        one_pass = ops[first:first + body]
        enters, leaves = self._loop_io

        def run_pass(t, carry):
            h, state, states = carry
            vals, kept = {enters: h}, {}
            self._run(one_pass, params, state, kept, vals,
                      self._in_pass(where, t), ctx)
            h = vals[leaves[0]]
            with jax.named_scope("step_io"):
                return h, kept, jax.lax.dynamic_update_index_in_dim(
                    states, h, t, 0)

        h = values[enters]
        _, kept, states = jax.lax.fori_loop(
            0, passes, run_pass,
            (h, {name: caches[name] for name in self._loop_state},
             jnp.zeros((passes,) + h.shape, h.dtype)))
        new.update(kept)
        values.update((uid, states[t]) for t, uid in enumerate(leaves))
        self._run(ops[first + passes * body:], params, caches, new, values,
                  where, ctx)
        return values[self._final_uid], new

    def _run(self, ops, params, caches, new, values, where, ctx) -> None:
        """``ops`` in order, each under its name: inputs out of ``values``
        and outputs into it, its leaves out of ``caches`` and, as it left
        them, into ``new``."""
        for op in ops:
            ins = [values[t.uid] for t in op.inputs]
            state = caches.get(op.name)
            # an op that pages AND counts is handed both entries' leaves
            counted = caches.get(op.name + COUNTERS)
            if counted is not None:
                state = dict(state, **counted)
            # metadata at trace time only: the compiled program is the
            # same, and its instructions name the op that owns them
            with jax.named_scope(op.name):
                outs, state = op.serve_step(params, ins, state, where, ctx)
            if counted is not None:
                new[op.name + COUNTERS] = {leaf: state[leaf]
                                           for leaf in counted}
                state = {leaf: val for leaf, val in state.items()
                         if leaf not in counted}
            if state is not None:
                new[op.name] = state
            for t, val in zip(op.outputs, outs):
                values[t.uid] = val

    def _in_pass(self, where: ServeStep, t) -> ServeStep:
        """``where`` as pass ``t`` of a looped stack sees it: every page id
        moved into region ``t`` of the leaves (``t * num_pages`` on), the
        pool's sentinel to the end of ALL regions, which is what an op
        reads off its leaves as the sentinel (``leaf.shape[0]``)."""
        end = self._loop[2] * self.num_pages

        def region(pages):
            return None if pages is None else jnp.where(
                pages >= self.num_pages, end, pages + t * self.num_pages)

        with jax.named_scope("step_io"):
            return dataclasses.replace(
                where, table=region(where.table),
                write_pages=region(where.write_pages), no_page=end)

    def _walk_decode(self, params, caches, tokens, pos, table,
                     write_pages, write_rows):
        """One position of every slot: the (slots, V) logits + updated
        caches (the body of :meth:`decode_fn`, shared so that the
        sampled decode and the draft scan run the IDENTICAL
        arithmetic)."""
        logits, new = self._walk(
            params, caches, tokens[:, None],
            ServeStep("token", table, pos=pos, write_pages=write_pages,
                      write_rows=write_rows, no_page=self.num_pages))
        return logits[:, 0], new

    # ---- speculative decoding (docs/serving.md "Speculative
    # decoding & sampling") ----------------------------------------------
    def _walk_window(self, params, caches, window, pos, table,
                     write_pages, write_rows):
        """The W-position verify step: ``window`` (slots, W) int32
        tokens at global positions ``pos[i] .. pos[i]+W-1`` per slot.
        Returns the (slots, W, V) logits + updated caches.  Speculation
        requires ``pageable`` state: a carry cannot roll back to an
        accept point."""
        return self._walk(
            params, caches, window,
            ServeStep("window", table, pos=pos, write_pages=write_pages,
                      write_rows=write_rows, no_page=self.num_pages))

    def verify_fn(self, width: int, sampled: bool = False):
        """The jitted speculative-VERIFY program for one window width
        W (== the round's γ): run the target over ``[last_token, d_1,
        .., d_{W-1}]`` at positions ``pos .. pos+W-1`` per slot in ONE
        dispatch — window row t's logits decide the token at position
        ``pos+t+1``, compared against proposal ``d_{t+1}``.

        Greedy (``sampled=False``):
        ``fn(params, caches, first (slots,), d (slots, W), pos, table,
        wp (slots, W), wr (slots, W)) -> ((n_accept (slots,), out
        (slots, W)), caches)`` where ``out`` is the target argmax per
        row — rows ``< n_accept`` equal the accepted proposals and row
        ``n_accept`` (when < W) IS the correction token, so the host
        emits ``out[i, :min(n+1, W)]`` verbatim.  Bit-identical to
        sequential greedy decode by induction over accepted prefixes
        (the parity pin).

        Sampled (``sampled=True``) adds ``q (slots, W, V)`` draft
        probs + per-slot strategy arrays, and applies seeded
        rejection-sampling acceptance on device
        (:func:`sampling.speculative_accept`), preserving the target
        distribution exactly."""
        key = (int(width), bool(sampled))
        fn = self._verify_fns.get(key)
        if fn is not None:
            return fn
        if not self.supports_chunking:
            raise ValueError("speculative verify needs a chunkable "
                             "graph (LSTM state cannot roll back)")
        w = int(width)

        def verify(params, caches, first, d, pos, table, wp, wr):
            with jax.named_scope("speculate"):
                window = jnp.concatenate([first[:, None], d[:, :-1]],
                                         axis=1)
            logits, new = self._walk_window(params, caches, window, pos,
                                            table, wp, wr)
            with jax.named_scope("sample"):
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            with jax.named_scope("speculate"):
                eq = (d == tgt).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(eq, axis=1),
                                axis=1).astype(jnp.int32)
            return (n_acc, tgt), new

        def verify_s(params, caches, first, d, q, pos, table, wp, wr,
                     temp, top_k, top_p, seeds):
            slots = first.shape[0]
            with jax.named_scope("speculate"):
                window = jnp.concatenate([first[:, None], d[:, :-1]],
                                         axis=1)
            logits, new = self._walk_window(params, caches, window, pos,
                                            table, wp, wr)
            with jax.named_scope("sample"):
                flat = logits.reshape(slots * w, -1)
                rep = lambda a: jnp.repeat(a, w)
                p = sampling.filtered_probs(flat, rep(temp), rep(top_k),
                                            rep(top_p))
                p = p.reshape(slots, w, -1)
            with jax.named_scope("speculate"):
                base = jnp.repeat(sampling.request_keys(seeds), w, axis=0)
                tpos = (pos[:, None] + 1 + jnp.arange(w)).reshape(-1)
                akeys = sampling.position_keys(
                    base, tpos, sampling.STREAM_ACCEPT).reshape(slots, w, 2)
                rkeys = sampling.position_keys(
                    base, tpos, sampling.STREAM_RESIDUAL).reshape(slots, w,
                                                                 2)
                n_acc, out = sampling.speculative_accept(d, p, q, akeys,
                                                         rkeys)
            return (n_acc, out), new

        fn = verify_s if sampled else verify
        fn.__name__ += f"_{w}"      # a program a width: verify_4, verify_s_4
        fn = jax.jit(fn, donate_argnums=(1,))
        self._verify_fns[key] = fn
        return fn

    def draft_fn(self, gamma: int, sampled: bool = False):
        """The jitted γ-step DRAFT program: ONE dispatch scans γ decode
        steps of the draft graph — step t feeds the token at position
        ``pos+t`` (step 0: the stream's last token; later steps: the
        previous proposal), writes the draft's K/V row there, and
        proposes the token for position ``pos+t+1``.  After the scan
        the draft cache covers exactly ``pos .. pos+γ-1`` — with the
        no-bonus-token verify window the draft is exactly caught up
        after EVERY round, accepted or not, so there is no draft
        catch-up state to track.

        Greedy: ``fn(params, caches, first (slots,), pos, table, wp
        (γ, slots), wr (γ, slots)) -> (d (slots, γ), caches)``.
        Sampled adds strategy arrays and also returns the per-step
        draft distributions ``q (slots, γ, V)`` the rejection test
        needs."""
        key = (int(gamma), bool(sampled))
        fn = self._draft_fns.get(key)
        if fn is not None:
            return fn
        if not self.supports_chunking:
            raise ValueError("speculative draft needs a chunkable "
                             "graph (LSTM state cannot roll back)")

        def draft(params, caches, first, pos, table, wp, wr):
            def step(carry, xs):
                tok, kv = carry
                wp_t, wr_t, t = xs
                logits, kv = self._walk_decode(params, kv, tok, pos + t,
                                               table, wp_t, wr_t)
                with jax.named_scope("sample"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt, kv), nxt

            (_, new), d = jax.lax.scan(
                step, (first, caches),
                (wp, wr, jnp.arange(int(gamma))))
            with jax.named_scope("step_io"):
                return jnp.transpose(d), new             # (slots, γ)

        def draft_s(params, caches, first, pos, table, wp, wr, temp,
                    top_k, top_p, seeds):
            base = sampling.request_keys(seeds)

            def step(carry, xs):
                tok, kv = carry
                wp_t, wr_t, t = xs
                logits, kv = self._walk_decode(params, kv, tok, pos + t,
                                               table, wp_t, wr_t)
                with jax.named_scope("sample"):
                    q = sampling.filtered_probs(logits, temp, top_k, top_p)
                    keys = sampling.position_keys(base, pos + t + 1,
                                                  sampling.STREAM_DRAFT)
                    nxt = sampling.categorical(keys, q)
                return (nxt, kv), (nxt, q)

            (_, new), (d, q) = jax.lax.scan(
                step, (first, caches),
                (wp, wr, jnp.arange(int(gamma))))
            with jax.named_scope("step_io"):
                return (jnp.transpose(d),
                        jnp.transpose(q, (1, 0, 2))), new

        fn = draft_s if sampled else draft
        fn.__name__ += f"_{int(gamma)}"     # draft_4, draft_s_4
        fn = jax.jit(fn, donate_argnums=(1,))
        self._draft_fns[key] = fn
        return fn

    # ---- what the compiler made of the programs (ISSUES 25, 39) ----------
    def _program_specs(self, device=None):
        """``(key, trace name, jitted fn, abstract arguments)`` of every
        program this decoder has built, with the shapes and dtypes the
        engine calls it with (its warm-up's and its dispatches' are the
        same: ``engine._warmup``), so that lowering them again asks the
        compilation cache for the executable that serves.  ``key`` is the
        documented one (``jit_prefill.<bucket>``), the trace name what a
        profiler prints (:func:`program_name`).  ``device`` places every
        argument on one (possibly only DESCRIBED) device instead of the
        model's.  The parameters' shapes are the installed weights', or
        the graph's own (``model.parameters``) where none are installed;
        the pools are described from the layout, never touched: a
        compiled model with no weights and no pool can answer."""
        from jax.sharding import PartitionSpec, SingleDeviceSharding

        model = self.model
        mesh = model.mesh
        one = None if device is None else SingleDeviceSharding(device)
        if one is not None and mesh is not None and mesh.is_distributed:
            raise ValueError("pool_copies(device=...) describes ONE "
                             "device; this model's mesh is distributed")

        def spec(shape, dtype, sharding=None):
            return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                        sharding=one or sharding)

        if model._params:
            params = {k: spec(v.shape, v.dtype,
                              getattr(v, "sharding", None))
                      for k, v in model._params.items()}
        else:       # as init_layers would make and place them
            params = {p.name: spec(
                p.shape, model.config.param_dtype
                if p.dtype == "float32" else p.dtype,
                model._param_sharding(p)) for p in model.parameters}
        compute = model.config.compute_dtype
        caches = {}
        for name, ent in self.layout.items():
            dt = entry_dtype(ent, compute)
            caches[name] = {
                leaf: spec(shape, dt, mesh.sharding(PartitionSpec(
                    *ent["entries"][leaf]))
                    if mesh is not None and mesh.is_distributed else None)
                for leaf, shape in ent["shapes"].items()}

        def i32(*shape):
            return spec(shape, jnp.int32)

        def f32(*shape):
            return spec(shape, jnp.float32)

        s, pps = self.slots, self.pages_per_slot
        strategy = (f32(s), i32(s), f32(s), i32(s))  # temp,top_k,top_p,seeds
        step = (i32(s), i32(s), i32(s, pps), i32(s), i32(s))
        out = []
        for b, fn in sorted(self._prefill_fns.items()):
            out.append((f"jit_prefill.{b}", fn,
                        (i32(1, b), i32(pps), i32(), i32(), i32())))
        if self._decode_fn is not None:
            out.append(("jit_decode", self._decode_fn, step))
        if self._decode_sampled_fn is not None:
            out.append(("jit_decode_s", self._decode_sampled_fn,
                        step + strategy))
        for (w, sampled), fn in sorted(self._verify_fns.items()):
            window = (i32(s), i32(s, pps), i32(s, w), i32(s, w))
            if sampled:
                args = (i32(s), i32(s, w), f32(s, w, self._vocab)) \
                    + window + strategy
            else:
                args = (i32(s), i32(s, w)) + window
            out.append((f"jit_verify{'_s' if sampled else ''}.{w}", fn,
                        args))
        for (g, sampled), fn in sorted(self._draft_fns.items()):
            args = (i32(s), i32(s), i32(s, pps), i32(g, s), i32(g, s))
            out.append((f"jit_draft{'_s' if sampled else ''}.{g}", fn,
                        args + (strategy if sampled else ())))
        return [(key, program_name(fn), fn, (params, caches) + args)
                for key, fn, args in out]

    def _read_programs(self, device=None):
        """``{key: (trace name, pool-sized copies, owner table, owners
        the table leaves out)}``: each program lowered again with the
        shapes the engine calls it with and compiled (the persistent
        compilation cache answers where the serving executable came
        from it or went to it), its text read ONCE for both
        :meth:`pool_copies` and :meth:`program_op_tables` and not kept;
        a program already read for ``device`` is not compiled again."""
        leaves = {int(np.prod(shape))
                  for ent in self.layout.values() if ent["kind"] == "kv"
                  for shape in ent["shapes"].values()}
        owners = [op.name for op in self.model.layers] + list(SERVE_OWNERS)
        parts = {s for op in self.model.layers for s in op.scopes}
        out = {}
        for key, name, fn, args in self._program_specs(device):
            if (key, device) not in self._program_reads:
                lowered = fn.lower(*args)
                text = lowered.compile().as_text()
                table = table_from_hlo(text, owners, parts)
                self._program_reads[key, device] = (
                    count_copies(text, leaves), table,
                    unnamed_owners(lowered, table, owners))
            out[key] = (name,) + self._program_reads[key, device]
        return out

    def pool_copies(self, device=None) -> Dict[str, Dict[str, int]]:
        """What says from inside the program that the pools are not
        copied: ``{program: {"count", "bytes"}}`` — for every serving
        program this decoder has built (``jit_decode``, ``jit_prefill.
        <bucket>``, ``jit_verify.<W>``, ``jit_draft.<γ>``, the ``_s``
        sampled variants), the ``copy`` instructions of its COMPILED
        text whose element count is a K/V pool leaf's
        (:func:`count_copies`).  The stored form is chosen so that each
        reads 0 (``analysis/kv_memory.py``); under the old ``(pages,
        page, heads, head_dim)`` form the TPU compile of one decode
        layer held eight such copies, 100 MB each in the serve cell.

        Computed ON DEMAND, never by ``start()``
        (:meth:`_read_programs`).  ``device`` compiles for one given
        device instead — a TPU that ``jax.experimental.topologies`` only
        describes will do, which is how a test without a chip reads the
        TPU's compiler."""
        return {key: copies for key, (_, copies, _, _)
                in self._read_programs(device).items()}

    def program_op_tables(self, device=None) -> Dict[str, Dict[str, tuple]]:
        """Which graph op each instruction of each compiled serving
        program belongs to: ``{program name as the profiler prints it
        (``jit_prefill_512``, ``jit_decode``): {instruction name: (owner
        | None, part | None)}}`` for every program this decoder has
        built.  Owners are the graph ops' names and
        ``obs.device_ops.SERVE_OWNERS``, parts the scopes an op opens
        inside its own (``Op.scopes``: ``moe_router``, ``moe_experts``,
        ``moe_shared``); sum a profiler trace's operations inside a
        program's ``XLA Modules`` events by that program's table.  ON
        DEMAND like :meth:`pool_copies`, and one compile a program with
        it.  A compiled text that gives no instruction to an owner
        its lowered text traces is an executable the compilation cache
        kept from a tree whose programs had other scopes, or none (jax
        leaves metadata out of the cache's key;
        ``obs.device_ops.unnamed_owners``): an error, never a guess."""
        reads = self._read_programs(device).values()
        stale = {name: unnamed for name, _, _, unnamed in reads if unnamed}
        if stale:
            raise stale_cache_error(stale)
        return {name: table for name, _, table, _ in reads}

    def decode_attention(self) -> Dict[str, int]:
        """How many attention ops of the graph got which decode core when
        a token step was last traced (``jit_decode``, ``jit_decode_s``,
        the ``jit_draft.<γ>`` scan): ``{"paged", "gathered"}`` —
        ``"paged"`` reads the pool in place
        (:mod:`flexflow_tpu.ops.paged_decode_kernel`), ``"gathered"``
        writes each slot's page table out as a view first.  Noted by the
        op at trace time (``MultiHeadAttention.decode_core``), like
        ``FFModel.attention_kernels()``; all zero before a token step is
        traced."""
        def count(ops, kinds=("paged", "gathered")):
            cores = [getattr(op, "decode_core", None) for op in ops]
            return {core: cores.count(core) for core in kinds}

        out = count(self.model.layers)
        if self.windowed:   # by layer kind, where the graph has two
            out["windowed"] = count(op for op in self.model.layers
                                    if op.name in self.windowed)
        latent = self._of_kind("latent")
        if latent:          # one shared row a token: a kind of its own
            out["latent"] = count(latent)
        sparse = self._of_kind("sparse")
        if sparse:          # a learned selection: ``"rows"`` copies the
            # chosen rows out of the pools, ``"paged"`` reads the live pages
            # in place under the set as a mask, ``"gathered"`` masks the view
            out["sparse"] = count(sparse, ("rows", "paged", "gathered"))
        return out

    def _of_kind(self, kind):
        return [op for op in self.model.layers
                if getattr(op, "decode_kind", None) == kind]

    def chunk_attention(self) -> Dict[str, Dict[str, int]]:
        """How many (latent attention op, chunk program) pairs got which
        chunk core when the programs were traced (every bucket built):
        ``{"latent": {"kernel", "loop"}}`` — ``"kernel"`` keeps a key
        block's expanded keys, values and scores in VMEM
        (:mod:`flexflow_tpu.ops.latent_chunk_kernel`), ``"loop"`` is XLA's
        loop over key blocks — and, for attention with a learned selection,
        ``{"sparse": {"mask", "gather", "loop", "dense"}}``: the chosen set
        as a mask over the whole table at once, as gathered rows (no
        program takes that form yet), as a mask on key blocks under a loop,
        or nothing to choose (a table no longer than ``topk``).  Noted by
        the op at trace time (``chunk_core``), like
        :meth:`grouped_product`; ``{}`` for a graph without such an op."""
        out = {}
        for kind, names in (("latent", ("kernel", "loop")),
                            ("sparse", ("mask", "gather", "loop",
                                        "dense"))):
            ops = self._of_kind(kind)
            if ops:
                cores = [core for op in ops
                         for core in tuple(op.chunk_core.values())]
                out[kind] = {core: cores.count(core) for core in names}
        return out

    def grouped_product(self) -> Dict[str, int]:
        """How many (mixture-of-experts op, serving program) pairs got
        which grouped product when the programs were traced (every chunk
        bucket, the token step, a window): ``{"rows", "library"}`` —
        ``"rows"`` is the repo's own kernel
        (:mod:`flexflow_tpu.ops.grouped_matmul_kernel`), ``"library"``
        ``jax.lax.ragged_dot``.  Noted by the op at trace time
        (``MoE.grouped_product``), like :meth:`decode_attention`."""
        cores = [core for op in self.model.layers
                 for (kind, _), core in tuple(getattr(
                     op, "grouped_product", {}).items()) if kind != "forward"]
        return {core: cores.count(core) for core in ("rows", "library")}

    def _counted(self, nxt, new):
        """What a token step returns first: its tokens, and for a graph
        whose ops count on the device (``self.counters``) ``(tokens, a COPY
        of those counters)`` as the step left them.  The counters
        themselves live in the cache tree and are donated to the next
        program; a second output of the step is a buffer of its own, so it
        can ride the boundary's one fetch whenever that comes, and no
        other program ever reads a donated buffer (one that did, a copy
        dispatched behind the step, made the next dispatch wait for the
        device on a TPU).  The copy is XLA's own (the same value leaves
        the program twice), so it is traced under no scope and an owner
        table gives it to nobody.  A graph that counts nothing returns
        what it returned before."""
        if not self.counters:
            return nxt
        return nxt, {n: dict(new[n]) for n in self.counters}

    def step_tokens(self, out):
        """``(tokens, counters or None)`` of a token step's first
        output (:meth:`_counted`)."""
        return out if self.counters else (out, None)

    def moe_stats(self, host) -> Dict[str, Dict]:
        """``{op: {"held", "assignments", "load_max_over_mean",
        "token_steps", "untouched_share", "load"}}`` for every op that
        counts its routing on the device (``"counter"`` entries with a
        ``load`` leaf: the MoE's), from ``host``, a token step's counters
        fetched.  All of it is said of the experts the op HOLDS (``held``
        of them: every expert, or this chip's share of an expert-parallel
        deployment): the pairs that fell on them, the largest load over
        their mean, the share of (token step, held expert) nobody chose;
        and what the op says of its dispatch (``MoE.dispatch_stats``)."""
        ops = {op.name: op for op in self.model.layers}
        out = {}
        for n, c in (host or {}).items():
            if "load" not in c:
                continue
            load = np.asarray(c["load"], np.int64)
            steps = int(c["token_steps"])
            mean = float(load.mean())
            out[n] = {
                "held": int(load.size),
                "assignments": int(load.sum()),
                "load_max_over_mean": (float(load.max()) / mean
                                       if mean else 0.0),
                "token_steps": steps,
                "untouched_share": (float(c["untouched"])
                                    / (steps * load.size) if steps else 0.0),
                "load": load.tolist(), **ops[n].dispatch_stats(c)}
        return out

    def sparse_stats(self, host) -> Dict[str, Dict]:
        """``{op: {"topk", "queries", "dense_queries", "chosen_mean",
        "live_mean"}}`` for every attention op that counts its choosing on
        the device (``<op> + COUNTERS`` entries with a ``counts`` leaf),
        from ``host``, a token step's counters fetched: the live queries it
        served (chunk rows, token steps, window rows), those whose history
        was no longer than ``topk`` (dense attention), and the mean number
        of positions a query attended over and had live."""
        ops = {op.name + COUNTERS: op for op in self._of_kind("sparse")}
        return {ops[n].name: ops[n].selection_stats(c["counts"])
                for n, c in (host or {}).items() if n in ops}

    def moe_totals(self, host) -> Dict[str, int]:
        """What a ``decode_step`` span carries of those counters, summed
        over the ops, as they stood behind that step: ``moe_expert_steps``
        (token steps x experts HELD) and ``moe_untouched`` (of them, the
        experts no live token chose).  The difference between two spans is
        what the steps between them touched."""
        moe = [c for c in (host or {}).values() if "load" in c]
        if not moe:
            return {}
        return {"moe_expert_steps": sum(int(c["token_steps"])
                                        * int(np.size(c["load"]))
                                        for c in moe),
                "moe_untouched": sum(int(c["untouched"]) for c in moe)}

    def loop_stats(self, host) -> Dict:
        """``{"passes", "tokens", "loop_passes", "exits_by_pass",
        "exit_mass_by_pass"}`` of a stack the graph runs several times a
        token, from ``host``, a token step's counters fetched: what the exit
        gate counted of the live rows it served (``ExitGate.loop_stats``);
        ``{}`` for a graph without one."""
        for op in self.model.layers:
            counted = (host or {}).get(op.name)
            if counted is not None and hasattr(op, "loop_stats"):
                return {"passes": op.passes,
                        **op.loop_stats(counted["counts"])}
        return {}

    def span_totals(self, host) -> Dict[str, int]:
        """What a ``decode_step`` span carries of the ops' counters as they
        stood behind that step: :meth:`moe_totals`, and for a looped stack
        ``loop_tokens`` and ``loop_passes`` (the live rows the exit gate
        served, and the passes they were run through)."""
        loop = self.loop_stats(host)
        return {**self.moe_totals(host),
                **({"loop_tokens": loop["tokens"],
                    "loop_passes": loop["loop_passes"]} if loop else {})}

    # ---- shared-instance registry --------------------------------------
    @classmethod
    def for_model(cls, model, slots: int, max_seq: int,
                  page_size: int = 0, num_pages: int = 0,
                  prefill_chunk: int = 0) -> "GraphDecoder":
        """One decoder per (model, slots, max_seq, page geometry):
        engines sharing a geometry share the jitted prefill/decode
        programs (the compile cost is the startup cost, like the
        serving engine's bucket warmup).  The key is the RESOLVED
        geometry, not the raw args: a 0-default key would pin the
        FIRST construction's config values (a later
        ``cfg.serve_kv_page`` change would silently get the stale
        decoder), and an explicit value equal to the default would
        duplicate identical compiles under a second key."""
        cfg = model.config
        ps = int(page_size
                 or getattr(cfg, "serve_kv_page", 0)
                 or DEFAULT_PAGE_SIZE)
        pool = int(num_pages
                   or getattr(cfg, "serve_kv_pages", 0)
                   or (default_num_pages(slots, max_seq, ps)
                       if ps > 0 else 0))
        reg = model.__dict__.setdefault("_gen_decoders", {})
        # the chunk is part of the geometry: it ends the list of chunk
        # buckets, and sizes a windowed entry's rows
        chunk = int(prefill_chunk)
        key = (int(slots), int(max_seq), ps, pool, chunk)
        dec = reg.get(key)
        if dec is None:
            dec = cls(model, slots, max_seq, page_size=ps,
                      num_pages=pool, prefill_chunk=chunk)
            reg[key] = dec
        return dec
