"""GenerationEngine — iteration-level continuous batching over the
PAGED KV-cached decode path (docs/serving.md "Token generation" +
"Paged KV & prefix caching").

The fixed-shape :class:`~flexflow_tpu.serving.engine.ServingEngine`
coalesces whole requests into one dispatch; token generation is a
different shape of problem — a request is a *stream* whose cost is
unknown up front (EOS may land anywhere).  Run-to-completion batching
wastes every slot whose stream finished early, so this engine schedules
at ITERATION granularity: a fixed ``slots``-wide decode batch shares
one KV **page pool**, requests join a free slot at any step boundary,
every step runs ONE decode dispatch + ONE token fetch for the whole
batch (repo_lint RL010 bans any other host sync in the loop), and a
finished/cancelled stream frees its slot — and its pages — immediately.

The owned decode loop runs ONE STEP AHEAD of the host (ISSUE 34): the
bookkeeping of a token step (lengths, pages, retirement by
``max_new_tokens``) advances by COUNTS when the step is dispatched, the
next step takes its input tokens from the device's own output
(:func:`_splice_tokens`), and a boundary's results — the step's tokens
and the first token of the join prefilled at that boundary — come to the
host in one fetch AFTER the next step is dispatched (``_land``), so the
device's work and both round trips lie beside the host's phases instead
of after them.  What needs token VALUES on the host (a speculative
round, a disaggregated hand-off, the fleet's ``dispatch_pending``) runs
the same boundary with nothing in flight.

Three ISSUE 15 mechanisms ride on the page pool:

* **Paged KV** — per-slot state is a page table of gather indices into
  fixed-size pool pages (``pages.KVPagePool``), so HBM-in-use scales
  with live tokens; ``analysis.kv_memory.kv_page_plan`` is the ONE
  accounting both this engine and lint/explain/the fleet gate read.
* **Shared-prefix reuse** — a ref-counted trie over full pages of
  prompt token ids (``pages.PrefixCache``): a prompt extending a
  cached prefix borrows the shared pages and prefills only its suffix.
  Shared pages are immutable by construction (see pages.py), LRU
  eviction frees unreferenced ones under pool pressure, and
  ``serve_prefix_cache=off`` disables the whole path with bit-identical
  tokens either way — the correctness anchor.
* **Chunked prefill** — long prompts prefill in ``serve_prefill_chunk``
  -token chunks, at most ONE chunk per decode-step boundary
  (Sarathi-style), so a long join stalls in-flight streams by one
  bounded chunk instead of one monolithic prompt.  ``0`` = whole-prompt
  chunks (the pre-paging behavior, program-for-program).

Admission reuses PR 8's machinery unchanged: the same
:class:`~flexflow_tpu.serving.batcher.MicroBatcher` (1 row per request)
provides the bounded queue with block/reject/shed_oldest policies,
per-request deadlines (a prompt still queued past its deadline expires
BEFORE any prefill is burned) and priority classes with the
anti-starvation aging bound — overload semantics carry over verbatim.

Strategy-sharded serving: :meth:`GenerationEngine.from_strategy` loads
a searched ``.pb``, re-places the params under the strategy's
PartitionSpecs (the SNIPPETS partition-rule → spec-pytree pattern) and
shards the pool's head dim over the ``c`` mesh axis
(analysis.kv_memory), so one checkpoint decodes tensor-parallel over
whatever mesh the strategy was searched for.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import faults
from ...compile_cache import enable as _enable_compile_cache
from ...fflogger import get_logger
from ...obs import lockwatch
from ...obs.device_ops import (SERVE_OWNERS, stale_cache_error,
                               table_from_hlo, unnamed_owners)
from ...obs.flight import flight_dump, get_flight
from ...obs.trace import phase_of, tracer_from_config
from ...profiling import quantiles
from ..batcher import MicroBatcher, Request
from ..errors import (GenerationCancelled, KVCacheExhausted,
                      OverloadError, SheddedError)
from ..metrics import ServingMetrics
from .decoder import GraphDecoder, program_name as _program_name
from .pages import KVPagePool, PrefixCache, export_pages, import_pages
from .sampling import SamplingParams

_END = object()  # token-stream sentinel


def _resolve(fut: Future, out) -> bool:
    """Complete a stream future with a result or exception, from EITHER
    lifecycle state: pending (failure paths fire before the engine
    claimed it at prefill) or running (the decode loop claimed it).
    Unlike the serving engine's ``_resolve_future`` this must NOT call
    ``set_running_or_notify_cancel`` — on an already-claimed (RUNNING)
    future that raises and would silently swallow the resolution.
    Cancelled/finished futures return False (client interference is a
    drop, never a dispatcher-thread exception)."""
    try:
        if isinstance(out, BaseException):
            fut.set_exception(out)
        else:
            fut.set_result(out)
        return True
    except Exception:  # noqa: BLE001 — InvalidStateError & kin
        return False


class GenerationStream:
    """Client handle for one generation request: iterate it for tokens
    as they retire per decode step, or wait on :meth:`result` for the
    full sequence.

    ::

        stream = engine.submit([1, 2, 3], max_new_tokens=16)
        for tok in stream:          # yields as decode steps complete
            ...
        final = stream.result()     # np.int32 array of all new tokens

    ``cancel()`` is safe at any time: a queued request is dropped
    before any prefill; a cancel landing mid-prefill (between chunks,
    or between the prefill dispatch and its scatter) or mid-generation
    frees its KV slot AND pages at the next step boundary and fails
    ONLY this stream with
    :class:`~flexflow_tpu.serving.errors.GenerationCancelled` — tokens
    already iterated remain valid."""

    def __init__(self, prompt_len: int, max_new: int, t_submit: float,
                 deadlined: bool = False, trace: Optional[str] = None,
                 sampling: Optional[SamplingParams] = None,
                 handoff=None):
        self.future: Future = Future()
        # disaggregated prefill/decode (docs/serving.md): when set, the
        # engine offers this stream's KV page chain to the callable at
        # prefill completion (``handoff(payload) -> bool``); True means
        # a DECODE engine adopted the stream and the source frees its
        # slot, False/raise falls back to co-located decode failing
        # nothing.  Set at submit() — the router's migration hook.
        self.handoff = handoff
        self.prompt_len = int(prompt_len)
        self.max_new = int(max_new)
        self.t_submit = t_submit
        self.deadlined = deadlined
        # per-request sampling strategy (None/greedy keeps the stream
        # on the unsampled argmax programs — the bit-parity anchor)
        self.sampling = sampling
        # sampled trace id (obs.trace) or None; the engine records this
        # stream's queue/prefill/terminal spans against it
        self.trace = trace
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._tokens: List[int] = []  # engine-thread writes, then frozen
        self._cancelled = threading.Event()
        # submit -> first token, set by the engine at the final prefill
        # chunk (None until then) — per-stream SLO evidence for the
        # goodput sweep
        self.ttft: Optional[float] = None

    # ---- client side ---------------------------------------------------
    def cancel(self) -> None:
        """Request cancellation.  Queued: the engine drops the request
        without a prefill (the future flips cancelled).  Prefilling or
        generating: the slot and its pages free at the next step
        boundary and the future fails with GenerationCancelled."""
        self._cancelled.set()
        # succeeds only while still queued (the engine claims the
        # future before prefill); a claimed future fails at the next
        # step boundary instead
        if self.future.cancel():
            self._q.put(_END)

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def tokens_so_far(self) -> List[int]:
        """Snapshot of the tokens retired so far (grows per step)."""
        return list(self._tokens)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """The full generated sequence (np.int32, length <= max_new) —
        blocks until EOS/max-tokens; raises the stream's failure."""
        return self.future.result(timeout)

    # ---- engine side ---------------------------------------------------
    def _emit(self, tok: int) -> None:
        self._tokens.append(tok)
        self._q.put(tok)

    def _finish(self) -> bool:
        done = _resolve(self.future, np.asarray(self._tokens, np.int32))
        self._q.put(_END)
        return done

    def _fail(self, exc: BaseException) -> bool:
        done = _resolve(self.future, exc)
        if done:
            self._q.put(exc)
        self._q.put(_END)
        return done


class _GenRequest(Request):
    """A queued prompt: a 1-row batcher Request carrying its stream.

    Deliberately NO ``stale=`` predicate: a cancelled-while-queued
    stream is already dropped at join time (the engine's
    ``set_running_or_notify_cancel`` claim fails on a cancelled
    future, so no prefill is burned), and a stale hook on EVERY
    request would flip the batcher's ``_watch`` fast path permanently
    on — every ``reap_expired()``/``poll()`` the decode loop runs
    would scan the whole queue under the lock even when nothing
    carries a deadline."""

    __slots__ = ("stream",)

    def __init__(self, stream: GenerationStream, prompt: np.ndarray,
                 on_done, t_submit: float, deadline=None, priority=0):
        super().__init__((prompt,), 1, on_done, t_submit,
                         deadline=deadline, priority=priority)
        self.stream = stream


class _Slot:
    """Dispatcher-thread-only state of one decode slot: its stream,
    its page list (prefix-cache hits first, private pages after), and
    its prefill progress.  ``prefilling`` slots own pages but are
    excluded from decode dispatch writes (their write page rides the
    pool's OOB sentinel)."""

    __slots__ = ("stream", "prompt", "pages", "draft_pages",
                 "hit_tokens", "next_pos", "chunks", "last_token",
                 "length", "generated", "prefilling", "t_join", "t_exec",
                 "unfetched", "done")

    def __init__(self, stream: GenerationStream, prompt: np.ndarray,
                 hit_pages: List[int], page_size: int, t_join: float):
        self.stream = stream
        self.prompt = prompt
        self.pages: List[int] = list(hit_pages)
        # the slot's pages in the DRAFT pool under speculation (no
        # prefix sharing: draft rows are never promoted to the trie)
        self.draft_pages: List[int] = []
        self.hit_tokens = len(hit_pages) * int(page_size)
        self.next_pos = self.hit_tokens  # next prompt position to prefill
        self.chunks = 0
        # ``length`` / ``generated`` are COUNTS and advance when a
        # program is dispatched; ``last_token`` is a VALUE and follows
        # when it is fetched: it is the slot's newest token only while
        # ``unfetched`` (tokens computed for the slot that are still on
        # the device) is 0.  ``done``: the stream was handed its end
        # (finished, failed, cancelled) — what is still in flight for it
        # is dropped.
        self.last_token = 0
        self.length = 0     # positions materialized in the cache
        self.generated = 0
        self.unfetched = 0
        self.done = False
        self.prefilling = True
        self.t_join = t_join
        # dispatch instant of the slot's FIRST prefill chunk — read only
        # while tracing (it splits `prefill` into wait and work)
        self.t_exec: Optional[float] = None


class _Flight:
    """What ONE step boundary left on the device until its one fetch
    (``GenerationEngine._land``): the token step's ``nxt`` with the
    slots it advanced (``rows``: ``(slot, _Slot, last)``, ``last`` = the
    stream retires on this token by ``max_new_tokens``, decided by count
    at dispatch), and the join's ``first`` token (``join``: ``(slot,
    _Slot, last, bucket, fn)``), and a copy of what the graph's ops have
    counted so far (``counters``: a second output of the token step,
    ``GraphDecoder.step_tokens``; ``None`` where nothing counts)."""

    __slots__ = ("nxt", "rows", "fn", "t0", "step", "first", "join",
                 "counters")

    def __init__(self):
        self.nxt = self.fn = self.first = self.join = self.counters = None
        self.rows: List = []
        self.t0 = 0.0
        self.step = 0

    def __bool__(self) -> bool:
        return self.nxt is not None or self.join is not None


def _splice_tokens(prev, host_tokens, from_host, first, join_slot):
    """The next token step's input, made ON the device: the last step's
    output ``prev`` where a slot's newest token has not reached the host,
    ``host_tokens`` where it has (``from_host``; 0 for a slot that does
    not decode, as before), and the joined slot's ``first`` token at
    ``join_slot`` (``slots`` = no join: the write drops)."""
    with jax.named_scope("step_io"):
        tokens = jnp.where(from_host, host_tokens, prev)
        return tokens.at[join_slot].set(first, mode="drop")


# a program name of its own like the decoder's (``jit_splice_tokens`` in a
# trace), and NEW with the scope: the scope changes no instruction and jax
# leaves metadata out of the compilation cache's key, so under the name it
# had a cache could answer with the scope-less program of an older tree
_splice_tokens.__name__ = "splice_tokens"
_splice_tokens = jax.jit(_splice_tokens)


class GenerationMetrics(ServingMetrics):
    """ServingMetrics plus the generation gauges: windowed tokens/s,
    TTFT (submit -> first token, i.e. queue wait + prefill) and TPOT
    (decode-step wall time — the per-token latency every active stream
    pays) percentiles, token/prefill totals, and — when the engine
    wires ``pool_stats_fn`` — the page-pool view (kv_pages_in_use,
    prefix_hit_rate, evictions, prefill_chunks).  Emitted as
    ``gen_stats`` events, the generation analogue of ``serve_stats``."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._ttfts: deque = deque(maxlen=4096)  # guarded_by: self._lock
        self._steps: deque = deque()             # guarded_by: self._lock
        # the engine's page-pool/prefix-cache snapshot provider (plain
        # attribute like queue_depth_fn; released with it)
        self.pool_stats_fn = None
        # token/prefill lifetime totals live in the obs.registry like
        # every other serving counter — gen_stats events and /metrics
        # read the same children (docs/observability.md "Metrics")
        from ...obs.registry import get_registry
        reg = get_registry()
        kv = {"model": self.model_tag, "eng": self.eng_id}
        # into self._fams too: unregister() must reclaim these series
        # with the rest (the fleet's bounded-retirement scheme)
        self._fams["tokens"] = reg.counter(
            "ff_gen_tokens_total", "Tokens generated (incl. the "
            "prefill's first token)", ("model", "eng"))
        self._fams["prefills"] = reg.counter(
            "ff_gen_prefills_total", "Prefill completions (stream "
            "joins)", ("model", "eng"))
        self._ctr["tokens"] = self._fams["tokens"].labels(**kv)
        self._ctr["prefills"] = self._fams["prefills"].labels(**kv)
        # speculative-decoding counters (ISSUE 16): registry-backed so
        # gen_stats events and the /metrics scrape read the SAME
        # children and can never diverge.  accept_rate in snapshot()
        # is derived from these two totals, not tracked separately.
        self._fams["draft_dispatches"] = reg.counter(
            "ff_gen_draft_dispatches_total", "Speculative draft "
            "dispatches (one γ-step scan per round)", ("model", "eng"))
        self._fams["spec_proposed"] = reg.counter(
            "ff_gen_spec_proposed_tokens_total", "Draft tokens "
            "proposed to the verifier", ("model", "eng"))
        self._fams["spec_accepted"] = reg.counter(
            "ff_gen_spec_accepted_tokens_total", "Draft tokens the "
            "verifier accepted", ("model", "eng"))
        self._fams["spec_fallbacks"] = reg.counter(
            "ff_gen_spec_fallbacks_total", "Demotions to plain decode "
            "(draft failure or accept-rate collapse)", ("model", "eng"))
        for k in ("draft_dispatches", "spec_proposed", "spec_accepted",
                  "spec_fallbacks"):
            self._ctr[k] = self._fams[k].labels(**kv)
        # the engine's live speculation view (current γ, policy, state)
        # merged into snapshot() like pool_stats_fn
        self.spec_stats_fn = None

    @property
    def total_tokens(self) -> int:
        return int(self._ctr["tokens"].value)

    @property
    def total_prefills(self) -> int:
        return int(self._ctr["prefills"].value)

    def record_ttft(self, seconds: float) -> None:
        now = self.clock()
        self._ctr["prefills"].inc()
        with self._lock:
            self._ttfts.append((now, float(seconds)))

    def record_decode_step(self, ntokens: int, step_s: float) -> None:
        now = self.clock()
        self._ctr["tokens"].inc(int(ntokens))
        with self._lock:
            self._steps.append((now, int(ntokens), float(step_s)))
            horizon = now - self.window_s
            while self._steps and self._steps[0][0] < horizon:
                self._steps.popleft()

    def record_spec_round(self, proposed: int, accepted: int) -> None:
        """One speculative round: one draft dispatch, ``proposed``
        draft tokens judged, ``accepted`` of them kept."""
        self._ctr["draft_dispatches"].inc()
        self._ctr["spec_proposed"].inc(int(proposed))
        self._ctr["spec_accepted"].inc(int(accepted))

    def record_spec_fallback(self) -> None:
        self._ctr["spec_fallbacks"].inc()

    def record_prefill_token(self) -> None:
        """The prefill's first token counts toward tokens/s too."""
        now = self.clock()
        self._ctr["tokens"].inc()
        with self._lock:
            self._steps.append((now, 1, 0.0))
            # trim here too: a max_new_tokens=1 workload never calls
            # record_decode_step, and the window must stay bounded
            horizon = now - self.window_s
            while self._steps and self._steps[0][0] < horizon:
                self._steps.popleft()

    def release(self) -> None:
        # drop the engine-owned pool provider with the queue-depth one
        # (a retired engine must not be retained by the registry)
        self.pool_stats_fn = None
        self.spec_stats_fn = None
        super().release()

    def snapshot(self) -> Dict:
        snap = super().snapshot()
        now = self.clock()
        with self._lock:
            steps = list(self._steps)
            ttfts = [v for _, v in self._ttfts]
            total_tokens = self.total_tokens
            total_prefills = self.total_prefills
        span = self.window_s
        if steps:
            span = min(self.window_s, max(1e-6, now - steps[0][0]))
        toks = sum(s[1] for s in steps)
        tpots = [s[2] for s in steps if s[2] > 0]
        qt = quantiles(ttfts)
        qp = quantiles(tpots)

        def ms(v):
            return None if v != v else round(v * 1e3, 3)

        proposed = int(self._ctr["spec_proposed"].value)
        accepted = int(self._ctr["spec_accepted"].value)
        snap.update({
            "tokens_per_s": round(toks / span, 3),
            "tokens": total_tokens,
            "prefills": total_prefills,
            "ttft_p50_ms": ms(qt[0.5]), "ttft_p95_ms": ms(qt[0.95]),
            "ttft_p99_ms": ms(qt[0.99]),
            "tpot_p50_ms": ms(qp[0.5]), "tpot_p95_ms": ms(qp[0.95]),
            "tpot_p99_ms": ms(qp[0.99]),
            # speculation totals (under speculation a "step" is a
            # draft+verify ROUND, so tpot_* percentiles are per-round
            # walls — tokens_per_s stays the honest cross-mode metric)
            "draft_dispatches": int(
                self._ctr["draft_dispatches"].value),
            "spec_proposed_tokens": proposed,
            "spec_accepted_tokens": accepted,
            "accept_rate": (round(accepted / proposed, 4)
                            if proposed else 0.0),
            "spec_fallbacks": int(self._ctr["spec_fallbacks"].value),
        })
        for fn in (self.pool_stats_fn, self.spec_stats_fn):
            if fn is not None:
                snap.update(fn())
        return snap

    def emit(self, extra: Dict | None = None) -> None:
        # eng rides as an event field for the same reason as
        # serve_stats': the cluster router's scrape keys on it
        get_logger("serve").event("gen_stats", eng=self.eng_id,
                                  **self.snapshot(), **(extra or {}))


class GenerationEngine:
    """Continuous-batching token generation over a compiled+initialized
    FFModel LM graph.

    ::

        engine = GenerationEngine(model, slots=8, eos_id=0)
        with engine:
            stream = engine.submit(prompt_ids, max_new_tokens=32)
            for tok in stream: ...
            out = stream.result()

    Knobs resolve from ``model.config`` (``--serve-gen-slots``,
    ``--serve-gen-max-seq``, ``--serve-gen-max-new``, the paged-KV
    knobs ``--serve-kv-page``/``--serve-kv-pages``/
    ``--serve-prefix-cache``/``--serve-prefill-chunk``, and PR 8's
    ``--serve-max-queue-rows``/``--serve-admission``/
    ``--serve-starvation-ms`` for admission — the queue bound counts
    REQUESTS here, one row each) unless overridden.  ``clock``/``sleep``
    are injectable for deterministic fault tests (RL008)."""

    # speculation guardrails (class attrs so tests can tighten them):
    # a draft whose EWMA accept rate sits below _SPEC_COLLAPSE_ACCEPT
    # after _SPEC_COLLAPSE_MIN_PROPOSED proposals costs more than it
    # saves — demote to plain decode rather than burn a draft dispatch
    # per round for nothing
    _SPEC_COLLAPSE_MIN_PROPOSED = 64
    _SPEC_COLLAPSE_ACCEPT = 0.1
    _SPEC_EWMA_ALPHA = 0.2        # per-round accept/cost EWMA weight
    _SPEC_RETUNE_EVERY = 16       # adaptive γ re-pricing cadence

    def __init__(self, model, slots: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 max_queue_requests: Optional[int] = None,
                 admission: Optional[str] = None,
                 starvation_ms: Optional[float] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Optional[str] = None,
                 draft_model=None,
                 spec_gamma: Optional[int] = None,
                 spec_gamma_max: Optional[int] = None,
                 spec_policy: Optional[str] = None,
                 stats_every: int = 32, metrics_window_s: float = 30.0,
                 clock=time.monotonic, sleep=time.sleep,
                 name: str = "", device=None):
        assert model._compiled, "compile() + init_layers() the model first"
        _enable_compile_cache()
        cfg = model.config
        if getattr(cfg, "serve_quantize", "") or \
                getattr(model, "_quantized", ""):
            # weight quantization is a DENSE-serving feature (the fleet
            # schema rejects it on generation tenants for the same
            # reason): silently serving full-precision weights while
            # the operator budgets HBM for int8 would overcommit the
            # KV+weight capacity plan
            raise ValueError(
                "serve_quantize is not supported by the generation "
                "engine (weight quantization covers dense serving "
                "only); unset FFConfig.serve_quantize for this model")
        self.model = model
        # ``device`` pins THIS engine's dispatches to one jax device:
        # its params copy is committed there, and every program
        # (prefill/decode/verify) follows the committed operand, so
        # N co-resident engines drive N accelerators independently —
        # the disaggregated cluster's placement primitive (a second
        # host-platform CPU device stands in for the second chip in
        # single-host runs).  None = the model's own placement.
        self.device = device
        if device is None:
            self._params = model._params
        else:
            import jax
            self._params = jax.device_put(model._params, device)
        self.slots = int(slots or cfg.serve_gen_slots)
        seq_len = (model.input_tensors[0].shape[1]
                   if model.input_tensors else 0)
        self.max_seq = int(max_seq or cfg.serve_gen_max_seq or seq_len)
        self.max_new_tokens = int(max_new_tokens
                                  or cfg.serve_gen_max_new_tokens)
        self.eos_id = eos_id
        self.clock = clock
        self._sleep = sleep
        self.stats_every = int(stats_every)
        self.admission = (cfg.serve_admission if admission is None
                          else admission)
        self.max_queue_requests = int(
            cfg.serve_max_queue_rows if max_queue_requests is None
            else max_queue_requests)
        self._batcher = MicroBatcher(
            1, 0.0, clock=clock, max_queue_rows=self.max_queue_requests,
            admission=self.admission,
            starvation_ms=float(cfg.serve_starvation_ms
                                if starvation_ms is None
                                else starvation_ms))
        # tenant identity, stamped on gen_stats/gen_* events (fleet
        # co-residency: N engines in one process stay distinguishable;
        # FFConfig.serve_model_name is the single-engine default)
        self.name = str(name or cfg.serve_model_name)
        self.metrics = GenerationMetrics(
            window_s=metrics_window_s, clock=clock,
            queue_depth_fn=lambda: self._batcher.queue_depth,
            model=self.name)
        # observability plane: same contract as ServingEngine — one
        # lock-free `active` read per decode step when tracing is off,
        # flight taps installed for post-mortem dumps
        self._tracer = tracer_from_config(cfg)
        get_flight()
        # chunked prefill: at most one chunk per step boundary; 0 =
        # whole-prompt chunks (the monolithic baseline).  LSTM graphs
        # cannot chunk (cell state is not a program input mid-prompt).
        chunk = int(cfg.serve_prefill_chunk if prefill_chunk is None
                    else prefill_chunk)
        if chunk < 0:
            raise ValueError(f"serve_prefill_chunk must be >= 0, "
                             f"got {chunk}")
        self._decoder = GraphDecoder.for_model(
            model, self.slots, self.max_seq,
            page_size=int(page_size or 0), num_pages=int(num_pages or 0),
            prefill_chunk=chunk)
        self.page_size = self._decoder.page_size
        self.num_pages = self._decoder.num_pages
        # the ONE KV accounting (analysis.kv_memory): what lint's
        # FF108/FF121 gates and the fleet's FF130 gate charge for this
        # deployment is what the pool actually allocates
        from ...analysis.kv_memory import dtype_bytes, kv_page_plan
        self.kv_plan = kv_page_plan(
            model.layers,
            dict(model.mesh.sizes) if model.mesh is not None else None,
            self.slots, self.max_seq,
            kv_dtype_bytes=dtype_bytes(cfg.compute_dtype),
            page_size=self.page_size, num_pages=self.num_pages,
            prefill_chunk=chunk)
        self.kv_cache_bytes = self.kv_plan["total_bytes"]
        self.prefill_chunk = (chunk if self._decoder.supports_chunking
                              else 0)
        # shared-prefix cache: on unless configured off; needs the
        # paged attention path (and whole-prompt LSTM graphs have no
        # pageable state to share)
        pc = (cfg.serve_prefix_cache if prefix_cache is None
              else prefix_cache)
        # (GraphDecoder.refusal: the one gate; why it is off, where the
        # graph's state cannot lend pages, is in stats()["kv_pages"])
        self.prefix_cache_refused = self._decoder.refusal("prefix reuse")
        self.prefix_cache_enabled = (
            str(pc).lower() not in ("off", "0", "false", "no")
            and self.prefix_cache_refused is None)
        # dispatcher-thread-only state (single writer, no lock)
        self._slots_state: List[Optional[_Slot]] = [None] * self.slots
        self._pool = KVPagePool(self.num_pages, self.page_size)
        self._prefix: Optional[PrefixCache] = (
            PrefixCache(self._pool) if self.prefix_cache_enabled
            else None)
        self._table = np.full((self.slots, self._decoder.pages_per_slot),
                              self._pool.no_page, np.int32)
        self._prefill_q: deque = deque()  # (slot, _Slot) FIFO
        # migrated-stream inbox (disaggregated serving): the ROUTER's
        # handoff appends host-only payloads from the SOURCE engine's
        # dispatcher thread; this thread drains it at step boundaries
        # (CPython deque append/popleft are atomic — no lock, no
        # cross-engine lock-order edge for the fflock gate to flag)
        self._adopt_q: deque = deque()
        # per-migration wall costs (ms), export side and import side:
        # the REAL price of a migration on this substrate
        self.migrate_export_ms: List[float] = []
        self.migrate_import_ms: List[float] = []
        self._caches = None
        self._n_steps = 0
        # step boundaries begun, and the ONE read of `tracer.active`
        # each boundary makes for all its phases (_run_boundary)
        self._boundary = 0
        self._traced = False
        # the one-step-ahead pipeline: what this boundary is leaving on
        # the device (`_cur`), what the last one left there and nobody
        # has fetched (`_inflight`: at most ONE boundary, depth one), and
        # the device's own arrays the next splice starts from (the last
        # step's output, the last join's first token)
        self._cur = _Flight()
        self._inflight: Optional[_Flight] = None
        self._landing: List[_Flight] = []   # being fetched right now
        # what the graph's ops counted on the device (an MoE's routing),
        # as of the last token step landed: a host copy, for stats()
        self._counters_host = None
        self._prev_tokens = None
        self._prev_first = None
        self._pipe_ahead = 0
        self._pipe_drained: Dict[str, int] = {}
        self._pipe_dropped = 0
        # the open `generate.turn` phase: from the end of a decode's
        # deliver to the next boundary's begin (_open_turn)
        self._turn = None
        self._chunks_total = 0
        self._hit_tokens = 0
        self._prompt_tokens = 0
        # lifetime counters preserved across pool rebuilds (a poisoned
        # dispatch rebuilds pool+prefix; totals must stay monotonic)
        self._evictions_base = 0
        self._evict_scanned_base = 0
        self._pool_high_base = 0
        self.metrics.pool_stats_fn = self._pool_stats
        # ---- speculative decoding (docs/serving.md "Speculative
        # decoding & sampling"): a co-hosted DRAFT model proposes γ
        # tokens per round in one scanned dispatch; the target verifies
        # the whole window in one chunked-prefill-class dispatch.  The
        # draft owns its OWN page pool/table/caches with the SAME
        # geometry (its rows mirror the target's positions 1:1), and
        # the fleet gate charges them byte-for-byte.
        self.draft_model = draft_model
        self._draft_params = None
        self._draft_decoder = None
        self._draft_pool: Optional[KVPagePool] = None
        self._draft_table = None
        self._draft_caches = None
        self.draft_kv_cache_bytes = 0
        g = int(cfg.serve_spec_gamma if spec_gamma is None
                else spec_gamma) if draft_model is not None else 0
        gmax = int(getattr(cfg, "serve_spec_gamma_max", 4)
                   if spec_gamma_max is None else spec_gamma_max)
        pol = str(getattr(cfg, "serve_spec_policy", "fixed")
                  if spec_policy is None else spec_policy)
        if pol not in ("fixed", "adaptive"):
            raise ValueError(f"spec_policy must be 'fixed' or "
                             f"'adaptive', got {pol!r}")
        if draft_model is not None:
            assert draft_model._compiled, \
                "compile() + init_layers() the draft model first"
            if pol == "fixed" and g == 0:
                raise ValueError(
                    "draft_model given but speculation is off "
                    "(serve_spec_gamma=0, policy 'fixed'): set "
                    "--serve-spec-gamma >= 2 or policy 'adaptive'")
            if g != 0 and g < 2:
                raise ValueError(
                    f"spec_gamma must be 0 (off) or >= 2, got {g}: a "
                    f"1-row verify window lowers matrix-vector kernels "
                    f"whose bits drift from the full forward (same "
                    f"floor as slots/serve_buckets)")
            if gmax < max(g, 2):
                raise ValueError(f"spec_gamma_max {gmax} < gamma "
                                 f"{max(g, 2)}")
            why = self._decoder.refusal("speculative decoding")
            if why is not None:     # an LSTM carry, a windowed ring: neither
                raise ValueError(why)   # rolls back to an accept point
            self._draft_decoder = GraphDecoder.for_model(
                draft_model, self.slots, self.max_seq,
                page_size=self.page_size, num_pages=self.num_pages)
            if not self._draft_decoder.supports_chunking:
                raise ValueError("draft model must be a chunkable "
                                 "attention graph too")
            tv = self._decoder.model.layers[-1].outputs[0].shape[-1]
            dv = draft_model.layers[-1].outputs[0].shape[-1]
            if tv != dv:
                raise ValueError(f"draft vocab {dv} != target vocab "
                                 f"{tv}: the proposals would not be "
                                 f"token ids of the target")
            self.draft_kv_plan = kv_page_plan(
                draft_model.layers,
                dict(draft_model.mesh.sizes)
                if draft_model.mesh is not None else None,
                self.slots, self.max_seq,
                kv_dtype_bytes=dtype_bytes(cfg.compute_dtype),
                page_size=self.page_size, num_pages=self.num_pages)
            self.draft_kv_cache_bytes = self.draft_kv_plan["total_bytes"]
            if device is None:
                self._draft_params = draft_model._params
            else:
                import jax
                self._draft_params = jax.device_put(
                    draft_model._params, device)
            self._draft_pool = KVPagePool(self.num_pages, self.page_size)
            self._draft_table = np.full(
                (self.slots, self._draft_decoder.pages_per_slot),
                self._draft_pool.no_page, np.int32)
        self.spec_policy = pol
        self.spec_gamma_max = gmax
        # candidate γs the adaptive controller prices (fixed: just γ)
        if draft_model is None:
            self._spec_candidates: List[int] = []
        elif pol == "fixed":
            self._spec_candidates = [g]
        else:
            self._spec_candidates = sorted(
                {c for c in (2, 4, gmax) if 2 <= c <= gmax})
        self._spec_gamma = (self._spec_candidates[0]
                            if self._spec_candidates else 0)
        if pol == "fixed" and g:
            self._spec_gamma = g
        self._spec_on = draft_model is not None
        self._spec_rounds = 0
        self._accept_ewma: Optional[float] = None
        self._spec_seen_proposed = 0
        self._spec_costs: Dict[int, float] = {}  # per-γ round-wall EWMA
        # pool_copies()'s last answer; None until somebody asks
        self._pool_copies: Optional[Dict[str, Dict[str, int]]] = None
        self.metrics.spec_stats_fn = self._spec_stats
        self._gen_faults: List[Dict] = []
        # lifecycle (same single-use contract as ServingEngine)
        self._thread: Optional[  # guarded_by: self._lifecycle
            threading.Thread] = None
        self._stopped = False    # guarded_by: self._lifecycle
        self._draining = False   # guarded_by: self._lifecycle
        self._finalized = False  # guarded_by: self._lifecycle
        self._lifecycle = lockwatch.lock("GenerationEngine._lifecycle")
        self._closing = threading.Event()
        self._abort = threading.Event()
        self._shutdown_done = threading.Event()

    # ---- lifecycle -----------------------------------------------------
    def _warmup(self) -> None:
        """Compile every program the engine can dispatch BEFORE
        serving — the generation edition of ServingEngine's bucket
        warmup.  A chunk bucket compiled lazily mid-serving stalls
        the whole decode batch for the compile (measured ~0.6 s/bucket
        on CPU — every in-flight stream's TPOT eats it); paying all of
        it at start() keeps steady-state latency flat.  The dummy
        dispatches ride an all-sentinel page table, so every pool
        write DROPS — warmup leaves the pool bit-clean."""
        params = self._params
        no_table = np.full((self._decoder.pages_per_slot,),
                           self._pool.no_page, np.int32)
        first = None
        for b in self._decoder.buckets:
            fn = self._decoder.prefill_fn(b)
            tokens = np.zeros((1, b), np.int32)
            first, self._caches = fn(params, self._caches, tokens, no_table,
                                     np.int32(0), np.int32(0), np.int32(1))
        # the token step the way the loop calls it: its tokens spliced ON
        # the device from a join's first token and the last step's output
        # (a host array in their place is another program to XLA's cache,
        # and a compile inside the serving window).  The first splice has
        # no step behind it yet; the second is the loop's own.
        self._prev_first = first
        nobody = (np.zeros((self.slots,), np.int32),
                  np.ones((self.slots,), bool))
        for _ in range(2):
            out, self._caches = self._decoder.decode_fn()(
                params, self._caches, self._spliced(*nobody),
                np.zeros((self.slots,), np.int32),
                np.full((self.slots, self._decoder.pages_per_slot),
                        self._pool.no_page, np.int32),
                np.full((self.slots,), self._pool.no_page, np.int32),
                np.zeros((self.slots,), np.int32))
            nxt, counted = self._decoder.step_tokens(out)
            self._prev_tokens = nxt
        _, self._counters_host = jax.device_get((nxt, counted))
        if self._spec_on:
            self._warmup_spec()

    def _warmup_spec(self) -> None:
        """Compile the draft prefill buckets plus the draft-scan and
        verify programs for every candidate γ (greedy variants; the
        sampled ones compile on the first sampled request), and TIME
        one dummy round per γ — the calibrated per-dispatch cost the
        adaptive controller prices against the live accept rate.
        Sentinel tables again: warmup writes all drop."""
        dparams = self._draft_params
        ddec = self._draft_decoder
        no_row = np.full((ddec.pages_per_slot,),
                         self._draft_pool.no_page, np.int32)
        for b in ddec.buckets:
            fn = ddec.prefill_fn(b)
            _, self._draft_caches = fn(
                dparams, self._draft_caches, np.zeros((1, b), np.int32),
                no_row, np.int32(0), np.int32(0), np.int32(1))
        dtable = np.full((self.slots, ddec.pages_per_slot),
                         self._draft_pool.no_page, np.int32)
        vtable = np.full((self.slots, self._decoder.pages_per_slot),
                         self._pool.no_page, np.int32)
        tokens = np.zeros((self.slots,), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        for g in self._spec_candidates:
            dwp = np.full((g, self.slots), self._draft_pool.no_page,
                          np.int32)
            dwr = np.zeros((g, self.slots), np.int32)
            vwp = np.full((self.slots, g), self._pool.no_page, np.int32)
            vwr = np.zeros((self.slots, g), np.int32)
            dfn = ddec.draft_fn(g)
            vfn = self._decoder.verify_fn(g)
            # compile pass, then one timed pass = the per-γ cost seed
            for probe in range(2):
                t0 = self.clock()
                d, self._draft_caches = dfn(
                    dparams, self._draft_caches, tokens, pos, dtable,
                    dwp, dwr)
                (n_acc, out), self._caches = vfn(
                    self._params, self._caches, tokens, d, pos,
                    vtable, vwp, vwr)
                jax.device_get((n_acc, out))
                if probe:
                    self._spec_costs[g] = max(1e-6,
                                              self.clock() - t0)

    def start(self, warmup: bool = True) -> "GenerationEngine":
        with self._lifecycle:
            if self._stopped:
                raise RuntimeError(
                    "engine was stopped; create a new GenerationEngine "
                    "(decoders cache their compiled programs on the "
                    "model, so a fresh engine starts warm)")
            if self._thread is None:
                self._caches = self._decoder.init_cache()
                if self._spec_on:
                    self._draft_caches = self._draft_decoder.init_cache()
                if warmup:
                    self._warmup()
                self._gen_faults = _load_gen_faults()
                get_logger("serve").event(
                    "gen_engine_start", model=self.name, slots=self.slots,
                    max_seq=self.max_seq,
                    kv_cache_bytes=self.kv_cache_bytes,
                    kv_page_size=self.page_size,
                    kv_num_pages=self.num_pages,
                    prefix_cache=("on" if self.prefix_cache_enabled
                                  else "off"),
                    prefill_chunk=self.prefill_chunk,
                    admission=self.admission,
                    max_queue_requests=self.max_queue_requests,
                    **self._spec_stats())
                self._thread = threading.Thread(
                    target=self._decode_loop, name="ff-generate",
                    daemon=True)
                self._thread.start()
        return self

    def stop(self) -> None:
        """Close admissions, serve everything queued and in flight to
        completion, stop the dispatcher, emit final stats.  Idempotent;
        single-use (see start()).  For a BOUNDED shutdown that sheds
        stragglers, see :meth:`drain`."""
        to_fail: List[Request] = []
        err = now = None
        with self._lifecycle:
            self._closing.set()
            self._batcher.close()
            if self._thread is not None:
                # lock-ok: dispatcher never takes _lifecycle, so joining
                # it under the lock cannot deadlock
                self._thread.join()
                self._thread = None
                if not self._finalized:
                    self._finalized = True
                    self.metrics.emit(extra={"final": True,
                                             "slots": self.slots})
            else:
                now = self.clock()
                err = SheddedError(
                    "engine stopped before it was started")
                to_fail = self._batcher.fail_pending()
            self._stopped = True
        # resolve OUTSIDE _lifecycle: on_done's future callbacks take
        # locks the static graph cannot see through a stored callable
        for r in to_fail:
            r.on_done(err, now)
        # same registry retirement as ServingEngine.stop()
        self.metrics.release()
        self._shutdown_done.set()

    def drain(self, timeout: Optional[float] = None) -> Dict:
        """Bounded graceful shutdown: stop admitting, give in-flight
        generation ``timeout`` seconds, then shed the stragglers
        (queued prompts AND active streams fail with SheddedError).
        Returns the final stats snapshot; the engine is stopped
        afterwards."""
        with self._lifecycle:
            already = self._stopped or self._draining
            thread = self._thread
            if not already:
                self._draining = True
                self._closing.set()
                self._batcher.close()
        if already:
            self._shutdown_done.wait()
            return self.stats()
        get_logger("serve").event(
            "gen_drain", model=self.name, timeout_s=timeout,
            queue_depth=self._batcher.queue_depth)
        shed = 0
        if thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                self._abort.set()
                now = self.clock()
                for r in self._batcher.fail_pending():
                    if r.on_done(SheddedError(
                            f"engine drained with work still queued "
                            f"(drain timeout {timeout}s)"), now):
                        shed += 1
                thread.join(timeout)
        else:
            now = self.clock()
            for r in self._batcher.fail_pending():
                if r.on_done(SheddedError(
                        "engine drained before it was started"), now):
                    shed += 1
        with self._lifecycle:
            self._stopped = True
            self._draining = False
            self._thread = None
            first = not self._finalized
            self._finalized = True
        snap = self.stats()
        if first:
            self.metrics.emit(extra={"final": True, "slots": self.slots,
                                     "drain_shed": shed})
        self.metrics.release()
        self._shutdown_done.set()
        return snap

    def __enter__(self) -> "GenerationEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- fleet-managed (external) dispatch -----------------------------
    def begin_external_dispatch(self, warmup: bool = True
                                ) -> "GenerationEngine":
        """Fleet mode: ready the engine WITHOUT its own decode thread —
        a :class:`~flexflow_tpu.serving.fleet.FleetEngine` drives
        :meth:`dispatch_pending` decode steps from ONE shared
        dispatcher, interleaved with its co-resident tenants' dense
        dispatches under weighted-fair scheduling.  The producer side
        (submit, admission, deadlines) behaves exactly as under
        :meth:`start`."""
        with self._lifecycle:
            if self._stopped:
                raise RuntimeError(
                    "engine was stopped; create a new GenerationEngine")
            if self._thread is not None:
                raise RuntimeError(
                    "engine already runs its own decode thread")
            if self._caches is None:
                self._caches = self._decoder.init_cache()
                if self._spec_on:
                    self._draft_caches = self._draft_decoder.init_cache()
                if warmup:
                    self._warmup()
                self._gen_faults = _load_gen_faults()
                get_logger("serve").event(
                    "gen_engine_start", model=self.name, slots=self.slots,
                    max_seq=self.max_seq,
                    kv_cache_bytes=self.kv_cache_bytes,
                    kv_page_size=self.page_size,
                    kv_num_pages=self.num_pages,
                    prefix_cache=("on" if self.prefix_cache_enabled
                                  else "off"),
                    prefill_chunk=self.prefill_chunk,
                    admission=self.admission,
                    max_queue_requests=self.max_queue_requests,
                    external=True, **self._spec_stats())
        return self

    def dispatch_pending(self) -> Optional[float]:
        """Externally-driven decode step (fleet mode): expire queued
        deadlines, join queued prompts into free slots, advance prefill
        by at most one chunk, and advance every active stream one
        token.  Returns the wall seconds spent — the device-time the
        fleet's fair scheduler charges this tenant — or None when
        nothing was due.  It is the owned loop's boundary
        (:meth:`_run_boundary`) with NOTHING left in flight: the step it
        dispatched is fetched and delivered before it returns, so the
        seconds charged hold the whole step and the fleet never has to
        come back for tokens.  Error containment is the boundary's own
        (a poisoned step fails the active streams, the engine keeps
        serving)."""
        t0 = self.clock()
        adopted, progressed, stepped = self._run_boundary(ahead=False)
        if not stepped:
            return (max(0.0, self.clock() - t0)
                    if progressed or adopted else None)
        self._close_turn()   # what follows is the fleet's time
        return max(0.0, self.clock() - t0)

    @property
    def has_pending(self) -> bool:
        """Whether the engine has work an external dispatcher should
        schedule: occupied decode slots (active or prefilling), queued
        prompts, or migrated streams awaiting adoption."""
        return (any(s is not None for s in self._slots_state)
                or self._batcher.queue_depth > 0
                or len(self._adopt_q) > 0)

    # ---- producer side -------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               priority: int = 0,
               sampling: Optional[SamplingParams] = None,
               handoff=None) -> GenerationStream:
        """Queue one prompt (1-D int token ids) and return its
        :class:`GenerationStream`.  Thread-safe.

        ``max_new_tokens`` caps the stream (default from config);
        generation also ends at ``eos_id`` when the engine has one.
        ``deadline_ms``/``priority`` behave exactly like the serving
        engine's (PR 8): a prompt still queued at its deadline expires
        with DeadlineExceeded before any prefill is burned; under a
        full bounded queue the admission policy applies per request.

        ``sampling`` selects the request's decoding strategy
        (temperature/top-k/top-p, seeded — see
        :class:`~.sampling.SamplingParams`); None or temperature 0 is
        greedy argmax, and a batch with no sampled request dispatches
        the UNSAMPLED programs so the bit-parity pins hold exactly.

        ``handoff`` (disaggregated serving) is an optional
        ``callable(payload) -> bool`` the engine offers the stream's
        exported KV pages to at prefill completion — True migrates the
        stream to a decode engine, False/raise keeps decoding here
        (see :meth:`adopt_migrated`)."""
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if arr.size < 1:
            raise ValueError("empty prompt")
        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            raise TypeError(f"sampling must be a SamplingParams, "
                            f"got {type(sampling).__name__}")
        # None-check, not truthiness: an explicit 0 must hit the guard
        # below, not silently fall back to the config default
        max_new = (self.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if arr.size + max_new > self.max_seq:
            raise ValueError(
                f"prompt ({arr.size}) + max_new_tokens ({max_new}) "
                f"exceeds the KV cache length max_seq={self.max_seq}")
        t0 = self.clock()
        self.metrics.record_submitted()
        tr = self._tracer
        trace = tr.new_trace() if tr.active else None
        stream = GenerationStream(arr.size, max_new, t0,
                                  deadlined=deadline_ms is not None,
                                  trace=trace, sampling=sampling,
                                  handoff=handoff)
        deadline = None if deadline_ms is None else t0 + deadline_ms / 1e3
        metrics = self.metrics
        trace_term = self._trace_terminal

        def on_done(out, now: float) -> bool:
            # failure-path resolution only (expiry/shed/drain/stop);
            # the success path is the decode loop's _finish
            if isinstance(out, BaseException):
                if stream._fail(out):
                    metrics.record_failure(out)
                    trace_term(stream, phase_of(out), now)
                    return True
            return False

        req = _GenRequest(stream, arr.copy(), on_done, t0,
                          deadline=deadline, priority=priority)
        req.trace = trace

        def count_cancel(f):
            # a cancel-while-QUEUED succeeds on the pending future and
            # no resolution path ever runs for it (the join claim just
            # drops the request) — count the submitted stream's
            # outcome at the cancel instant, or the submitted ==
            # outcomes reconciliation leaks one per cancel.  A
            # mid-generation cancel cannot reach here with
            # cancelled()=True (cancel() on a RUNNING future fails;
            # _retire counts it via record_failure instead).
            if f.cancelled():
                metrics.record_cancelled()
                trace_term(stream, "cancelled", self.clock())

        stream.future.add_done_callback(count_cancel)
        try:
            self._batcher.submit(req)
        except OverloadError:
            self.metrics.record_rejected()
            self._trace_terminal(stream, "rejected", self.clock())
            raise
        except RuntimeError as e:
            self.metrics.record_rejected()
            self._trace_terminal(stream, "rejected", self.clock())
            raise OverloadError(
                f"engine is not admitting new work ({e})") from e
        return stream

    def _trace_terminal(self, stream: GenerationStream, phase: str,
                        now: float) -> None:
        """Record the stream's ONE terminal `request` span (no-op for
        unsampled streams) — phase counts reconcile with the metrics
        counters exactly like the dense engine's."""
        if stream.trace is None:
            return
        self._tracer.span(
            "request", stream.trace, stream.t_submit, now,
            tid=self.name or "generate", phase=phase,
            tokens=len(stream._tokens), model=self.name)

    def _pool_stats(self) -> Dict:
        """The page-pool/prefix-cache snapshot merged into gen_stats
        and stats() — lifetime counters stay monotonic across the
        pool rebuilds a poisoned dispatch forces."""
        pool = self._pool
        prefix = self._prefix
        hw = max(self._pool_high_base, pool.high_water)
        prompt_toks = self._prompt_tokens
        return {
            "kv_page_size": self.page_size,
            "kv_num_pages": self.num_pages,
            "kv_pages_in_use": pool.pages_in_use,
            "kv_pages_high_water": hw,
            "kv_high_water_bytes":
                hw * self.kv_plan["page_bytes"]
                + self.kv_plan["window_bytes"]
                + self.kv_plan["state_bytes"],
            "prefix_cache": "on" if prefix is not None else "off",
            "prefix_hit_tokens": self._hit_tokens,
            "prefix_hit_rate": (round(self._hit_tokens
                                      / prompt_toks, 4)
                                if prompt_toks else 0.0),
            "prefix_pages_cached": len(prefix) if prefix else 0,
            "evictions": (self._evictions_base
                          + (prefix.evictions if prefix else 0)),
            "evict_scanned": (self._evict_scanned_base
                              + (prefix.evict_scanned if prefix else 0)),
            "prefill_chunks": self._chunks_total,
        }

    def _paged_caches(self) -> Dict:
        """The leaves that live in the shared pool's pages (what a
        migration ships): every ``"kv"`` entry of a graph the gate lets
        migrate; an op's counters stay where they are."""
        return {name: sub for name, sub in self._caches.items()
                if self._decoder.layout[name]["kind"] == "kv"}

    def _kv_pages_stats(self) -> Dict:
        """``stats()["kv_pages"]``, by kind of entry: the shared pool the
        host allocates from (``full``) and the rows windowed entries hold
        a slot, which it never touches (``windowed``)."""
        from ...analysis.kv_memory import dtype_bytes
        pool, plan = self._pool, self.kv_plan
        itemsize = dtype_bytes(self.model.config.compute_dtype)
        paged = {name: ent for name, ent in self._decoder.layout.items()
                 if ent["kind"] == "kv" and not ent.get("window")}
        out = {"full": {"entries": len(paged),
                        # a token's bytes by entry, as STORED (every leaf's
                        # declared width, lane padding included; a row in
                        # every pass's region where a looped stack calls
                        # the op several times a token)
                        "bytes_per_token": {
                            name: itemsize * ent.get("passes", 1)
                            * sum(int(shape[-1]) for shape
                                  in ent["shapes"].values())
                            for name, ent in paged.items()},
                        "num_pages": self.num_pages,
                        "in_use": pool.pages_in_use,
                        "free": pool.pages_free,
                        "high_water": max(self._pool_high_base,
                                          pool.high_water),
                        "bytes": plan["pool_bytes"]}}
        windowed = self._decoder.windowed
        if windowed:
            out["windowed"] = {
                "entries": len(windowed),
                "window": max(e["window"] for e in windowed.values()),
                "rows_per_slot": plan["window_rows"],
                "bytes": plan["window_bytes"],
                "refused": self.prefix_cache_refused}
        return out

    def pool_copies(self) -> Dict[str, Dict[str, int]]:
        """``{program: {"count", "bytes"}}``: the pool-sized ``copy``
        operations in the compiled text of every serving program this
        engine's decoders have built — the target's under their
        program names, the draft's under ``draft/`` — as
        :meth:`GraphDecoder.pool_copies` counts them.  0 everywhere
        says the stored form holds: no program pays for what the pool
        weighs.  ON DEMAND only (it lowers and compiles each program
        again; the compilation cache answers), so ``start()`` and the
        serving loop never pay for it; once asked, :meth:`stats`
        carries the answer under ``pool_copies``."""
        out = dict(self._decoder.pool_copies(device=self.device))
        if self._draft_decoder is not None:
            out.update(
                ("draft/" + key, val) for key, val in
                self._draft_decoder.pool_copies(
                    device=self.device).items())
        self._pool_copies = out
        return out

    def program_op_tables(self) -> Dict[str, Dict[str, tuple]]:
        """``{program name as a profiler trace prints it: {instruction
        name: (owner | None, part | None)}}``: which graph op (or which
        of ``obs.device_ops.SERVE_OWNERS``) each instruction of every
        serving program this engine's decoders have built belongs to —
        the draft's under ``draft/``, the token splice's under its own
        name — as :meth:`GraphDecoder.program_op_tables` reads them.
        ON DEMAND only, like :meth:`pool_copies`, with which it shares
        the one compile a program; it touches no weight and no pool, so
        an engine that was never started can answer for the programs
        its decoder has built."""
        out = dict(self._decoder.program_op_tables(device=self.device))
        if self._draft_decoder is not None:
            out.update(
                ("draft/" + name, table) for name, table in
                self._draft_decoder.program_op_tables(
                    device=self.device).items())
        slots = jax.ShapeDtypeStruct((self.slots,), np.int32)
        one = jax.ShapeDtypeStruct((), np.int32)
        splice = _program_name(_splice_tokens)
        lowered = _splice_tokens.lower(
            slots, slots, jax.ShapeDtypeStruct((self.slots,), bool),
            one, one)
        out[splice] = table_from_hlo(lowered.compile().as_text(),
                                     SERVE_OWNERS)
        unnamed = unnamed_owners(lowered, out[splice], SERVE_OWNERS)
        if unnamed:
            raise stale_cache_error({splice: unnamed})
        return out

    def stats(self) -> Dict:
        active = sum(1 for s in self._slots_state if s is not None)
        out = {**self.metrics.snapshot(), "slots": self.slots,
               "active_slots": active, "max_seq": self.max_seq,
               "kv_cache_bytes": self.kv_cache_bytes,
               "prefill_chunk": self.prefill_chunk,
               "admission": self.admission,
               "max_queue_requests": self.max_queue_requests,
               "peak_queue_requests": self._batcher.peak_rows,
               "decode_attention": self._decoder.decode_attention(),
               "kv_pages": self._kv_pages_stats(),
               # the one-step-ahead pipeline (docs/observability.md):
               # token steps dispatched before the previous step's
               # tokens were on the host, how often and why it ran
               # empty, tokens computed for a stream that had ended
               "decode_pipeline": {
                   "ahead": self._pipe_ahead,
                   "drained": dict(self._pipe_drained),
                   "dropped_tokens": self._pipe_dropped}}
        # what a token leaves in the shared pool, all entries (and, for a
        # looped stack, all passes) together
        out["kv_bytes_per_token"] = sum(
            out["kv_pages"]["full"]["bytes_per_token"].values())
        chunk_attention = self._decoder.chunk_attention()
        if chunk_attention:     # latent attention, or a learned selection
            out["chunk_attention"] = chunk_attention
        if self._pool_copies is not None:
            out["pool_copies"] = self._pool_copies
        # per expert the live tokens it received, as of the last token
        # step landed: the counters' copy rides that boundary's one fetch
        # (docs/observability.md)
        moe = self._decoder.moe_stats(self._counters_host)
        if moe:
            # beside the ops: which grouped product their programs took
            # at trace time (host memory only, like the rest)
            out["moe"] = {**moe, "grouped_product":
                          self._decoder.grouped_product()}
        # what attention with a learned selection chose, the same way
        sparse = self._decoder.sparse_stats(self._counters_host)
        if sparse:
            out["sparse_attention"] = sparse
        # a stack run several times a token: the passes the served rows
        # went through and the exit gate's mass by pass, the same way
        loop = self._decoder.loop_stats(self._counters_host)
        if loop:
            out.update(loop_passes=loop["loop_passes"],
                       exit_mass_by_pass=loop["exit_mass_by_pass"],
                       loop=loop)
        return out

    # ---- dispatcher thread ---------------------------------------------
    def _decode_loop(self) -> None:
        """One iteration per step boundary (:meth:`_run_boundary`), one
        step ahead of the host; with nothing to decode, nothing in
        flight and no prefill under way, wait for a prompt."""
        try:
            while True:
                if self._abort.is_set():
                    self._abort_active()
                    return
                _, progressed, stepped = self._run_boundary(ahead=True)
                if stepped or progressed or any(
                        s is not None for s in self._slots_state):
                    continue  # decoding, or prefill still in flight
                with self._phase("generate.idle"):
                    reqs = self._batcher.next_batch(timeout=0.05)
                if reqs:
                    for r in reqs:
                        self._assign(r)
                    continue
                if (self._closing.is_set()
                        and self._batcher.queue_depth == 0):
                    return
        finally:
            self._close_turn()

    def _phase(self, name: str, step_num: Optional[int] = None, **args):
        """One phase of this step boundary in both sinks
        (:meth:`~flexflow_tpu.obs.trace.Tracer.phase`): the profiler's
        annotation always, the engine-clock span when the boundary is
        traced.  Profiler-side names start ``generate`` / ``gen-prefill``
        so a trace reduction that keeps the engine's annotations keeps
        the phases too; ``step`` is the boundary's number, the
        identifier the phases of one boundary share."""
        return self._tracer.phase(
            name, self.clock, self._traced, step_num, cat="engine",
            tid=self.name or "generate", step=self._boundary, **args)

    def _open_turn(self) -> None:
        """After a decode's deliver: open the phase that lasts until the
        next boundary begins (or the fleet's ``dispatch_pending``
        returns).  The loop only turns round in it, yet on the chip the
        engine's thread stood there 2-3 ms at most boundaries of a
        128-client run (PERF.md, PR 24), so it has a name."""
        self._turn = self._phase("generate.turn")
        self._turn.__enter__()

    def _close_turn(self) -> None:
        turn, self._turn = self._turn, None
        if turn is not None:
            turn.__exit__(None, None, None)

    def _run_boundary(self, ahead: bool):
        """ONE step boundary, the body of the owned decode loop
        (``ahead`` True) and of the fleet's ``dispatch_pending`` (False):
        expire, adopt, admit; advance prefill by AT MOST one chunk (the
        decode-stall cap); grow the active slots' pages; dispatch ONE
        token step for the whole batch; then the boundary's ONE fetch
        (:meth:`_land`, RL010) and the hand-over to the streams.

        ``ahead`` is how many token steps may stay in flight past that
        fetch, 1 or 0.  One ahead, the fetch brings the PREVIOUS
        boundary's results (its step's tokens, its join's first token)
        and this boundary's stay on the device until the next one has
        dispatched its step: the device computes and the clients wake
        beside the host's phases, not after them.  What the step kind
        itself needs decides the same way, not a switch: a speculative
        round places its next window by the accept counts, so it runs
        with nothing in flight; a boundary with no slot left to decode
        has nothing to hide its fetch behind and takes it at once.

        The device sees the programs in the order the host dispatches
        them (step n, boundary n+1's prefill chunk, step n+1), so a page
        released BY COUNT when step n is dispatched can only be handed
        to a program that runs after the last one that reads or writes
        it.  Returns ``(adopted, progressed, stepped)``."""
        self._close_turn()
        self._boundary += 1
        # ONE lock-free tracing check per step boundary, handed down to
        # every phase (hot-path contract, docs/observability.md)
        self._traced = self._tracer.active
        with self._phase("generate.admit"):
            # expire queued deadlines at EVERY step boundary — with all
            # slots busy, _admit() never polls, and a deadline must
            # fail AT the deadline (PR 8's contract), not when a slot
            # happens to free
            self._batcher.reap_expired()
            adopted = self._join_adopted()
            self._admit()
        progressed = self._prefill_step()
        with self._phase("generate.grow_pages"):
            self._grow_active_pages()
        stepped = any(s is not None and not s.prefilling
                      for s in self._slots_state)
        try:
            if not stepped:
                self._drain("idle")
            elif self._spec_active():
                self._fire_slow_decode()
                self._drain("speculation")
                self._spec_decode_once()
            else:
                self._fire_slow_decode()
                self._decode_once(ahead)
        except BaseException as e:  # noqa: BLE001 — one poisoned step
            # must fail the ACTIVE streams (and those whose tokens were
            # still in flight), not kill the dispatcher; queued prompts
            # are still served
            self._recover_from_dispatch_error(e, "gen_decode_error")
        return adopted, progressed, stepped

    def _drain(self, reason: str) -> None:
        """Empty the pipeline: fetch and deliver whatever the last
        boundary and this one left on the device, with no step dispatched
        to hide the fetch behind.  Counted by ``reason`` when a token
        step was in flight (``stats()["decode_pipeline"]["drained"]``)."""
        prior, self._inflight = self._inflight, None
        cur, self._cur = self._cur, _Flight()
        if prior is not None:
            self._pipe_drained[reason] = \
                self._pipe_drained.get(reason, 0) + 1
        self._land(prior, cur)

    def _land(self, *flights) -> None:
        """THE one host sync of a step boundary (RL010): ONE
        ``device_get`` for everything ``flights`` (oldest first) left on
        the device — a step's tokens for the whole batch, a join's first
        token — then the hand-over to the streams, first tokens before
        step tokens so that a stream's tokens keep their order.  The
        clients wake here, straight after the fetch that follows a
        dispatch: beside the device's step, not beside the next
        boundary's admit / grow_pages / prepare."""
        flights = self._landing = [f for f in flights if f]
        if not flights:
            return
        with self._phase("generate.fetch"):
            host = jax.device_get([(f.nxt, f.first, f.counters)
                                   for f in flights])
        for f, (_, _, counted) in zip(flights, host):
            if counted is not None:
                f.counters = self._counters_host = counted
        now = self.clock()
        if any(f.join is not None for f in flights):
            with self._phase("gen-prefill.deliver"):
                for f, (_, first, _) in zip(flights, host):
                    if f.join is not None:
                        slot, st, last, bucket, fn = f.join
                        if self._deliver_first_token(slot, st, int(first),
                                                     now, bucket, fn):
                            self._retire(slot, st, last, now)
        if any(f.nxt is not None for f in flights):
            with self._phase("generate.deliver"):
                for f, (nxt, _, _) in zip(flights, host):
                    if f.nxt is not None:
                        self._deliver_step(f, nxt, now)
        self._landing = []

    def _admit(self) -> None:
        """Join queued prompts into free slots at the step boundary —
        the continuous-batching join point.  Assignment is instant
        (prefix-cache lookup + slot bookkeeping); the prefill itself
        runs chunk-by-chunk at later boundaries."""
        for slot in range(self.slots):
            if self._slots_state[slot] is not None:
                continue
            batch = self._batcher.poll()
            if not batch:
                return
            for r in batch:
                self._assign(r, slot)

    def _assign(self, req: _GenRequest,
                slot: Optional[int] = None) -> None:
        if slot is None or self._slots_state[slot] is not None:
            slot = next((i for i, s in enumerate(self._slots_state)
                         if s is None), None)
            if slot is None:
                # unreachable from the loop (joins only happen with a
                # free slot), but never strand a stream if it ever is
                req.stream._fail(SheddedError(
                    "internal: no free decode slot at join"))
                return
        stream = req.stream
        try:
            claimed = stream.future.set_running_or_notify_cancel()
        except RuntimeError:
            claimed = False
        if not claimed:
            return  # cancelled/expired while queued (the cancel was
            #         counted at cancel() time — see submit())
        prompt = req.xs[0]
        hits: List[int] = []
        if self._prefix is not None:
            hits = self._prefix.lookup(prompt)
        st = _Slot(stream, prompt, hits, self.page_size, self.clock())
        for i, pg in enumerate(hits):
            self._table[slot, i] = pg
        self._slots_state[slot] = st
        self._prefill_q.append((slot, st))
        self._prompt_tokens += int(prompt.size)
        self._hit_tokens += st.hit_tokens

    # ---- paged prefill (chunked) ---------------------------------------
    def _prefill_step(self) -> bool:
        """Advance prefill by AT MOST one chunk dispatch per step
        boundary (Sarathi-style): a long joining prompt stalls
        in-flight decode by one bounded chunk, never one monolithic
        prompt.  Returns True when a chunk (or a prefill-side
        retirement) happened."""
        while self._prefill_q:
            slot, st = self._prefill_q[0]
            if self._slots_state[slot] is not st or not st.prefilling:
                self._prefill_q.popleft()  # slot retired/reassigned
                continue
            if st.stream.cancelled:
                # cancel landed between chunks (or between the claim
                # and the first chunk): free the slot AND its pages
                # without burning another dispatch
                self._prefill_q.popleft()
                self._fail_slot(slot, st, GenerationCancelled(
                    f"stream cancelled during prefill after "
                    f"{st.chunks} chunk(s); KV slot {slot} and "
                    f"{len(st.pages)} page(s) freed"), "cancelled")
                return True
            return self._run_chunk(slot, st)
        return False

    def _run_chunk(self, slot: int, st: _Slot) -> bool:
        """Dispatch ONE prefill chunk for the queue-head slot.  The
        final chunk activates the slot BY COUNT (it decodes at this very
        boundary, its first token spliced into the step's input on the
        device) and leaves that token on the device for the boundary's
        one fetch; a stream whose first token is needed on the host at
        once — a ``handoff`` wants it in its payload, a speculative
        round starts from it — fetches it here, as every join once
        did."""
        prompt = st.prompt
        start = st.next_pos
        remaining = int(prompt.size) - start
        chunk = (remaining if self.prefill_chunk <= 0
                 else min(self.prefill_chunk, remaining))
        with self._phase("gen-prefill.prepare"):
            if not self._ensure_pages(slot, st, start + chunk):
                self._prefill_q.popleft()
                self._fail_slot(slot, st, KVCacheExhausted(
                    f"no KV page free for prefill at position {start} "
                    f"(pool {self.num_pages} pages, "
                    f"{self._pool.pages_in_use} in use, prefix cache "
                    f"fully referenced)"), "shed")
                return True
            bucket = self._decoder.prefill_bucket(chunk)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :chunk] = prompt[start:start + chunk]
            fn = self._decoder.prefill_fn(bucket)
            row = self._table[slot].copy()
        final = start + chunk >= int(prompt.size)
        at_once = final and (st.stream.handoff is not None
                             or self._spec_active())
        tok = 0
        program = None
        if self._traced:
            program = _program_name(fn)
            if st.t_exec is None:
                st.t_exec = self.clock()
        try:
            # the span says which chunk of the prompt this is, how long,
            # and which program ran it (the name a device trace prints)
            with self._phase("gen-prefill", step_num=self._n_steps,
                             chunk=st.chunks, length=chunk, bucket=bucket,
                             program=program):
                first, self._caches = fn(
                    self._params, self._caches, tokens, row,
                    np.int32(slot), np.int32(start), np.int32(chunk))
                if at_once:
                    tok = int(jax.device_get(first))
        except BaseException as e:  # noqa: BLE001 — a poisoned chunk
            # fails the joining stream AND (because the dispatch may
            # have consumed the donated cache pytree) every in-flight
            # stream; the engine re-arms and keeps serving the queue
            self._prefill_q.popleft()
            if st.stream._fail(e):
                self.metrics.record_failure(e)
                self._trace_terminal(st.stream, "error", self.clock())
            st.done = True
            self._recover_from_dispatch_error(e, "gen_prefill_error")
            return True
        st.next_pos = start + chunk
        st.chunks += 1
        self._chunks_total += 1
        if not final:
            return True  # next chunk at a later step boundary
        self._prefill_q.popleft()
        # the join BY COUNT: the slot decodes from this boundary on
        st.prefilling = False
        st.length = int(prompt.size)
        st.generated = 1
        st.unfetched += 1
        last = st.generated >= st.stream.max_new
        if self._prefix is not None:
            # promote the freshly-computed full prompt pages (the hit
            # prefix re-touches its nodes' LRU stamps); whoever reads
            # them is dispatched after this chunk
            full = max(0, (int(prompt.size) - 1) // self.page_size)
            self._prefix.insert(prompt, st.pages[:full])
        self._prev_first = first
        if not at_once:
            self._cur.first = first
            self._cur.join = (slot, st, last, bucket, fn)
            if last:
                self._release_slot(slot, st)
            return True
        now = self.clock()
        with self._phase("gen-prefill.deliver"):
            self._deliver_first_token(slot, st, tok, now, bucket, fn)
            stream = st.stream
            if stream.handoff is not None and not (
                    last or (self.eos_id is not None
                             and tok == self.eos_id)):
                # disaggregated serving: offer the freshly-prefilled KV
                # page chain to the router's handoff.  Streams retiring
                # at this very boundary (max_new=1, first token is EOS)
                # stay local — migrating them would ship pages nothing
                # decodes.
                if self._migrate_out(slot, st, now):
                    return True
            if self._spec_active():
                self._draft_prefill(slot, st)
            self._retire(slot, st, last, now)
        return True

    def _deliver_first_token(self, slot: int, st: _Slot, tok: int,
                             now: float, bucket: int, fn) -> bool:
        """The join's hand-over at ``now``, the instant its first token
        reached the host: emit the token and record the request's road
        here.  False when the stream had ended before its first token
        arrived (the token is dropped)."""
        st.unfetched -= 1
        if st.done:
            self._pipe_dropped += 1
            return False
        st.last_token = tok
        stream = st.stream
        stream.ttft = now - stream.t_submit
        stream._emit(tok)
        self.metrics.record_ttft(stream.ttft)
        self.metrics.record_prefill_token()
        if self._traced and stream.trace is not None:
            tname = self.name or "generate"
            args = dict(slot=slot, phase="target",
                        prompt_len=int(st.prompt.size),
                        prefix_hit_tokens=st.hit_tokens,
                        prefill_chunks=st.chunks)
            self._tracer.span("queue", stream.trace, stream.t_submit,
                              st.t_join, tid=tname, slot=slot)
            self._tracer.span("prefill", stream.trace, st.t_join, now,
                              tid=tname, **args)
            if st.t_exec is not None:
                # `prefill` split at the dispatch of the slot's first
                # chunk: queue + prefill_wait + prefill_exec tile
                # submit() to the first token exactly
                self._tracer.span("prefill_wait", stream.trace,
                                  st.t_join, st.t_exec, tid=tname,
                                  slot=slot)
                self._tracer.span("prefill_exec", stream.trace,
                                  st.t_exec, now, tid=tname,
                                  step=self._boundary, bucket=bucket,
                                  program=_program_name(fn), **args)
        return True

    # ---- disaggregated prefill/decode migration ------------------------
    def _migrate_out(self, slot: int, st: _Slot, now: float) -> bool:
        """Export the slot's KV pages + stream state and offer them to
        ``stream.handoff``.  True = a decode engine adopted the stream:
        the source frees the slot (shared prefix pages stay cached —
        the trie holds its own references).  False = fallback to
        co-located decode with ONE ``serve_health`` event and NO stream
        failed — the slot is untouched either way until adoption is
        confirmed."""
        stream = st.stream
        t0 = self.clock()
        try:
            why = self._decoder.refusal("KV migration")
            if why is not None:
                raise RuntimeError(why)
            e0 = time.perf_counter()
            host = export_pages(self._paged_caches(), st.pages,
                                self.num_pages,
                                pad_to=self._decoder.pages_per_slot)
            self.migrate_export_ms.append(
                (time.perf_counter() - e0) * 1e3)
            # charge only the REAL chain (the pad rows are a fixed-
            # shape compile-cache artifact, not shipped state)
            nbytes = sum(int(a.nbytes) for sub in host.values()
                         for a in sub.values()) * len(st.pages) // max(
                             len(st.pages), self._decoder.pages_per_slot)
            payload = {
                "stream": stream,
                "prompt": st.prompt,
                "pages": host,
                "pages_used": len(st.pages),
                "nbytes": nbytes,
                "page_size": self.page_size,
                "last_token": int(st.last_token),
                "length": int(st.length),
                "generated": int(st.generated),
                "source": self.name,
            }
            adopted = bool(stream.handoff(payload))
        except BaseException as e:  # noqa: BLE001 — a failed export or
            # handoff must cost this stream NOTHING but staying local
            self._migrate_fallback(slot, e)
            return False
        if not adopted:
            self._migrate_fallback(slot, None)
            return False
        if self._tracer.active and stream.trace is not None:
            self._tracer.span("migrate", stream.trace, t0, self.clock(),
                              tid=self.name or "generate", slot=slot,
                              phase="export", pages=len(st.pages),
                              bytes=payload["nbytes"])
        # the destination owns the stream now: free the slot WITHOUT
        # finishing it.  release() drops the slot's references only —
        # prefix pages the trie promoted stay resident here, so a
        # same-prefix prompt still hits.
        self._release_slot(slot, st)
        return True

    def _migrate_fallback(self, slot: int, exc) -> None:
        """Migration declined/failed: one health event (mirror of
        ``_spec_demote`` — NO stream fails, decode continues
        co-located on this engine) plus a flight dump when it was an
        error rather than a routing decision."""
        err = ("" if exc is None
               else f"{type(exc).__name__}: {exc}"[:300])
        get_logger("serve").event(
            "serve_health", model=self.name, component="migration",
            status="fallback", slot=slot,
            reason=("handoff_declined" if exc is None
                    else "handoff_error"),
            error=err, step=self._n_steps)
        if exc is not None:
            flight_dump("gen_migrate_error",
                        extra={"model": self.name, "slot": slot,
                               "error": err, "step": self._n_steps})

    def adopt_migrated(self, payload: Dict) -> bool:
        """Decode-engine side of migration: enqueue an
        :func:`~.pages.export_pages` payload (plus stream state) for
        adoption at this engine's next dispatch boundary.  Thread-safe
        (the source engine's dispatcher calls this through the router's
        handoff): the payload is host-only data and the deque append is
        atomic — the import itself runs on THIS engine's dispatch
        thread, which owns the pool/caches (single-writer
        discipline)."""
        with self._lifecycle:
            if self._stopped or self._closing.is_set():
                return False
        self._adopt_q.append(payload)
        return True

    def _join_adopted(self) -> bool:
        """Import ONE queued migrated stream into a free slot
        (dispatcher thread).  A payload with no free slot waits at the
        queue head — slots free as streams retire.  One adoption per
        dispatch boundary bounds the decode-step gap co-hosted streams
        pay for an arriving migration burst by a single import; the
        queue drains across consecutive turns (``has_pending`` keeps
        the dispatcher coming back).  Returns True when a stream
        joined."""
        if not self._adopt_q:
            return False
        if not any(s is None for s in self._slots_state):
            return False
        try:
            payload = self._adopt_q.popleft()
        except IndexError:
            return False
        self._import_migrated(payload)
        return True

    def _import_migrated(self, payload: Dict) -> None:
        """Allocate destination pages, scatter the payload in with one
        ``device_put`` (:func:`~.pages.import_pages`), and seat the
        stream in a free slot mid-generation — decode continues here
        bit-for-bit where the source's prefill left off.  The prompt's
        full pages are promoted into THIS engine's prefix trie (the
        accounting parity with a co-located join); pool exhaustion is
        the same legitimate shed as a co-located allocation failure."""
        stream: GenerationStream = payload["stream"]
        prompt = np.asarray(payload["prompt"], np.int32).reshape(-1)
        now = self.clock()
        slot = next((i for i, s in enumerate(self._slots_state)
                     if s is None), None)
        if slot is None:  # _join_adopted guards this; never strand
            self._adopt_q.appendleft(payload)
            return
        first = next(iter(next(iter(payload["pages"].values())).values()))
        need = int(payload.get("pages_used") or first.shape[0])
        pages: List[int] = []
        while len(pages) < need:
            pg = self._alloc_page()
            if pg is None:
                break
            pages.append(pg)
        if len(pages) < need or int(payload["page_size"]) != \
                self.page_size:
            for pg in pages:
                self._pool.release(pg)
            exc = KVCacheExhausted(
                f"cannot adopt migrated stream: need {need} page(s) "
                f"of size {payload['page_size']} (pool {self.num_pages} "
                f"pages of {self.page_size}, {self._pool.pages_in_use} "
                f"in use)")
            if stream._fail(exc):
                self.metrics.record_failure(exc)
                self._trace_terminal(stream, "shed", now)
            return
        try:
            i0 = time.perf_counter()
            why = self._decoder.refusal("KV migration")
            if why is not None:
                raise ValueError(why)
            self._caches = {**self._caches, **import_pages(
                self._paged_caches(), payload["pages"], pages,
                self.num_pages)}
            self.migrate_import_ms.append(
                (time.perf_counter() - i0) * 1e3)
        except BaseException as e:  # noqa: BLE001 — a poisoned import
            # fails only the migrating stream (import_pages validates
            # every leaf BEFORE its first donating scatter, so a graph
            # or geometry mismatch leaves the resident pool untouched)
            for pg in pages:
                self._pool.release(pg)
            if stream._fail(e):
                self.metrics.record_failure(e)
                self._trace_terminal(stream, "error", now)
            return
        st = _Slot(stream, prompt, [], self.page_size, now)
        st.hit_tokens = 0
        st.pages = pages
        st.prefilling = False
        st.length = int(payload["length"])
        st.next_pos = st.length
        st.last_token = int(payload["last_token"])
        st.generated = int(payload["generated"])
        for i, pg in enumerate(pages):
            self._table[slot, i] = pg
        self._slots_state[slot] = st
        if self._tracer.active and stream.trace is not None:
            self._tracer.span("migrate", stream.trace, now, self.clock(),
                              tid=self.name or "generate", slot=slot,
                              phase="import", pages=len(pages),
                              bytes=int(payload.get("nbytes", 0)),
                              source=str(payload.get("source", "")))
        if self._prefix is not None:
            full = max(0, (int(prompt.size) - 1)) // self.page_size
            self._prefix.insert(prompt, st.pages[:full])
        if self._spec_active():
            # speculative decoding composes with disaggregation by
            # co-hosting the draft with the DECODE engine: mirror the
            # prompt into the draft cache exactly like a local join
            self._draft_prefill(slot, st)

    # ---- page bookkeeping ----------------------------------------------
    def _alloc_page(self) -> Optional[int]:
        """One page from the pool, LRU-evicting unreferenced prefix
        pages under pressure; None only when every page backs a live
        slot (the caller sheds the stream)."""
        pg = self._pool.alloc()
        while pg is None and self._prefix is not None \
                and self._prefix.evict(1):
            pg = self._pool.alloc()
        return pg

    def _ensure_pages(self, slot: int, st: _Slot,
                      upto_pos: int) -> bool:
        """Grow the slot's page table to cover positions
        ``[0, upto_pos)``.  The whole deficit is evicted up front, as
        one batch of the prefix cache's leaf order: a victim costs a
        pop of that order, not a walk of the trie, whether the deficit
        is a joining prompt's six pages or the one page of each
        decoding slot that crosses a page edge."""
        need = (int(upto_pos) - 1) // self.page_size + 1
        deficit = need - len(st.pages) - self._pool.pages_free
        if deficit > 0 and self._prefix is not None:
            self._prefix.evict(deficit)
        while len(st.pages) < need:
            pg = self._alloc_page()
            if pg is None:
                return False
            self._table[slot, len(st.pages)] = pg
            st.pages.append(pg)
        return True

    def _grow_active_pages(self) -> None:
        """Before a decode dispatch: every active slot needs a page for
        the position it is about to write.  A slot the pool cannot
        serve (undersized ``serve_kv_pages`` with the prefix cache
        fully referenced) is shed — only that stream fails."""
        for i, s in enumerate(self._slots_state):
            if s is None or s.prefilling:
                continue
            if not self._ensure_pages(i, s, s.length + 1):
                self._fail_slot(i, s, KVCacheExhausted(
                    f"no KV page free for decode at position "
                    f"{s.length} (pool {self.num_pages} pages, "
                    f"{self._pool.pages_in_use} in use)"), "shed")

    def _release_slot(self, slot: int, st: _Slot) -> None:
        """Return the slot's pages to the pool (shared prefix pages
        just drop one reference — the trie keeps them cached) and
        clear its table row back to the OOB sentinel."""
        for pg in st.pages:
            self._pool.release(pg)
        st.pages = []
        self._table[slot, :] = self._pool.no_page
        if self._draft_pool is not None:
            for pg in st.draft_pages:
                self._draft_pool.release(pg)
            self._draft_table[slot, :] = self._draft_pool.no_page
        st.draft_pages = []
        self._slots_state[slot] = None

    def _fail_slot(self, slot: int, st: _Slot, exc: BaseException,
                   phase: str) -> None:
        now = self.clock()
        if st.stream._fail(exc):
            self.metrics.record_failure(exc)
            self._trace_terminal(st.stream, phase, now)
        self._settle(slot, st)

    def _settle(self, slot: int, st: _Slot) -> None:
        """The stream was handed its end: what is still in flight for it
        is dropped when it lands, and the slot goes back where the count
        (a retirement by ``max_new_tokens``) has not freed it already."""
        st.done = True
        if self._slots_state[slot] is st:
            self._release_slot(slot, st)

    # ---- decode --------------------------------------------------------
    def _spec_active(self) -> bool:
        return self._spec_on and self._spec_gamma >= 2

    def _batch_sampling(self) -> bool:
        """Whether ANY active slot carries a non-greedy strategy — the
        routing bit: all-greedy batches dispatch the UNSAMPLED programs
        so the bit-parity pins never depend on the sampled kernels."""
        for s in self._slots_state:
            if s is None or s.prefilling or s.stream.sampling is None:
                continue
            if not s.stream.sampling.is_greedy:
                return True
        return False

    def _sampling_arrays(self):
        """Per-slot strategy arrays for the sampled programs; inactive
        and greedy slots ride the defaults (temp 0 -> exact one-hot
        argmax inside the kernel)."""
        temp = np.zeros((self.slots,), np.float32)
        top_k = np.zeros((self.slots,), np.int32)
        top_p = np.ones((self.slots,), np.float32)
        seeds = np.zeros((self.slots,), np.int32)
        for i, s in enumerate(self._slots_state):
            if s is None or s.prefilling or s.stream.sampling is None:
                continue
            sp = s.stream.sampling
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            seeds[i] = sp.seed
        return temp, top_k, top_p, seeds

    def _spliced(self, host_tokens, from_host, join_slot=None):
        """The token step's input ON the device (:func:`_splice_tokens`):
        the last step's output where a slot's newest token is still
        there, ``host_tokens`` where ``from_host``, this boundary's
        join's first token at ``join_slot``.  With no step or join
        behind it yet (an engine started without warm-up) the host's
        arrays stand in."""
        prev = self._prev_tokens
        first = self._prev_first
        return _splice_tokens(
            host_tokens if prev is None else prev, host_tokens, from_host,
            np.int32(0) if first is None else first,
            np.int32(self.slots if join_slot is None else join_slot))

    def _decode_once(self, ahead: bool, why: str = "external") -> None:
        """Advance the whole decode batch one position: one dispatch,
        the slots' bookkeeping moved on BY COUNT, then the boundary's
        one fetch — of the step before this one when running ``ahead``
        (this step's tokens stay on the device until the next boundary
        has dispatched), of this step too when not (counted under
        ``why``).  Write pages/rows are host-computed — inactive and
        PREFILLING slots ride the pool's OOB sentinel so their dummy
        writes drop instead of corrupting a (possibly shared) page."""
        cur = self._cur
        with self._phase("generate.prepare"):
            tokens = np.zeros((self.slots,), np.int32)
            from_host = np.ones((self.slots,), bool)
            pos = np.zeros((self.slots,), np.int32)
            wp = np.full((self.slots,), self._pool.no_page, np.int32)
            wr = np.zeros((self.slots,), np.int32)
            for i, s in enumerate(self._slots_state):
                if s is not None and not s.prefilling:
                    if s.unfetched:
                        from_host[i] = False   # its token is on the device
                    else:
                        tokens[i] = s.last_token
                    pos[i] = s.length
                    wp[i] = self._table[i, s.length // self.page_size]
                    wr[i] = s.length % self.page_size
                    # the step BY COUNT: the slot is one position and
                    # one token further once this step is dispatched
                    s.length += 1
                    s.generated += 1
                    s.unfetched += 1
                    cur.rows.append(
                        (i, s, s.generated >= s.stream.max_new))
            table = self._table.copy()
            sampled = self._batch_sampling()
            if sampled:
                temp, top_k, top_p, seeds = self._sampling_arrays()
        cur.t0 = self.clock()
        cur.step = self._n_steps
        with jax.profiler.StepTraceAnnotation("generate",
                                              step_num=self._n_steps):
            with self._phase("generate.dispatch"):
                spliced = self._spliced(
                    tokens, from_host,
                    None if cur.join is None else cur.join[0])
                if sampled:
                    cur.fn = self._decoder.decode_sampled_fn()
                    out, self._caches = cur.fn(
                        self._params, self._caches, spliced, pos, table,
                        wp, wr, temp, top_k, top_p, seeds)
                else:
                    cur.fn = self._decoder.decode_fn()
                    out, self._caches = cur.fn(
                        self._params, self._caches, spliced, pos, table,
                        wp, wr)
                cur.nxt, cur.counters = self._decoder.step_tokens(out)
        self._n_steps += 1
        self._prev_tokens = cur.nxt
        # a slot that retires on this token frees its pages NOW, behind
        # the step that wrote them: whichever program takes them next is
        # dispatched after it
        for i, s, last in cur.rows:
            if last:
                self._release_slot(i, s)
        prior, self._inflight = self._inflight, None
        self._cur = _Flight()
        if ahead:
            if prior is not None:
                self._pipe_ahead += 1
            self._inflight = cur
            self._land(prior)
        else:
            self._pipe_drained[why] = self._pipe_drained.get(why, 0) + 1
            self._land(prior, cur)
        self._open_turn()

    def _deliver_step(self, f: _Flight, host: np.ndarray,
                      now: float) -> None:
        """Hand a fetched token step to its streams at ``now``: each
        slot's token, then its stream's end where the token is its last
        (by count), the EOS, or a cancel arrived.  A stream that had
        ended before this step's tokens reached the host (EOS or a
        cancel are found one step late) gets nothing: the token is
        dropped and counted."""
        emitted = 0
        for i, s, last in f.rows:
            s.unfetched -= 1
            if s.done:
                self._pipe_dropped += 1
                continue
            s.last_token = tok = int(host[i])
            s.stream._emit(tok)
            emitted += 1
            self._retire(i, s, last, now)
        if self._traced:
            self._tracer.span("decode_step", None, f.t0, now,
                              tid=self.name or "generate",
                              step=f.step, active=len(f.rows),
                              phase="decode",
                              program=_program_name(f.fn),
                              **self._decoder.span_totals(f.counters))
        self.metrics.record_decode_step(emitted, now - f.t0)
        self._fire_cancel_at_token(f.rows, now)
        if self.stats_every and (f.step + 1) % self.stats_every == 0:
            self.metrics.emit(extra={"slots": self.slots,
                                     "active": len(f.rows)})

    # ---- speculative round ---------------------------------------------
    def _spec_decode_once(self) -> None:
        """One speculative ROUND for the whole batch: the draft scans
        γ decode steps in ONE dispatch, the target verifies the whole
        window in ONE dispatch (the slot-batched chunked-prefill
        kernel), and ONE host fetch brings back the accept counts plus
        the emit-ready token rows — 2 dispatches + 1 sync per up-to-γ
        tokens, vs γ of each for plain decode (RL010's budget, spent
        better).

        No rollback state: ``out[i, :min(n+1, γ)]`` is emitted verbatim
        (accepted proposals then the correction), the draft cache is
        exactly caught up after every round by construction (the
        no-bonus window), and rows written beyond the accept point stay
        invisible behind the causal mask until overwritten.  Trailing
        pages past the accepted length go back to the pools
        immediately."""
        g = self._spec_gamma
        # provision BOTH pools for the whole window up front; positions
        # past max_seq ride the sentinel (their writes drop, and the
        # prompt+max_new<=max_seq budget retires the stream before any
        # such row could be emitted)
        with self._phase("generate.grow_pages"):
            for i, s in enumerate(self._slots_state):
                if s is None or s.prefilling:
                    continue
                upto = min(s.length + g, self.max_seq)
                if not self._ensure_pages(i, s, upto):
                    self._fail_slot(i, s, KVCacheExhausted(
                        f"no KV page free for a γ={g} verify window at "
                        f"position {s.length} (pool {self.num_pages} "
                        f"pages, {self._pool.pages_in_use} in use)"),
                        "shed")
                    continue
                if not self._ensure_draft_pages(i, s, upto):
                    self._fail_slot(i, s, KVCacheExhausted(
                        f"no DRAFT KV page free at position {s.length} "
                        f"(draft pool {self.num_pages} pages, "
                        f"{self._draft_pool.pages_in_use} in use)"), "shed")
        active = [(i, s) for i, s in enumerate(self._slots_state)
                  if s is not None and not s.prefilling]
        if not active:
            return
        nactive = len(active)
        with self._phase("generate.prepare"):
            tokens = np.zeros((self.slots,), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            vwp = np.full((self.slots, g), self._pool.no_page, np.int32)
            vwr = np.zeros((self.slots, g), np.int32)
            dwp = np.full((g, self.slots), self._draft_pool.no_page,
                          np.int32)
            dwr = np.zeros((g, self.slots), np.int32)
            for i, s in active:
                tokens[i] = s.last_token
                pos[i] = s.length
                for t in range(g):
                    p = s.length + t
                    if p >= self.max_seq:
                        break  # sentinel stays: the write drops
                    vwp[i, t] = self._table[i, p // self.page_size]
                    vwr[i, t] = p % self.page_size
                    dwp[t, i] = self._draft_table[i, p // self.page_size]
                    dwr[t, i] = p % self.page_size
            sampled = self._batch_sampling()
            if sampled:
                temp, top_k, top_p, seeds = self._sampling_arrays()
        traced = self._traced   # the boundary's one read of the gate
        t0 = self.clock()
        try:
            self._fire_spec_draft_fail()
            with jax.profiler.StepTraceAnnotation(
                    "gen-draft", step_num=self._n_steps):
                if sampled:
                    dfn = self._draft_decoder.draft_fn(g, sampled=True)
                    (d, q), self._draft_caches = dfn(
                        self._draft_params, self._draft_caches,
                        tokens, pos, self._draft_table.copy(), dwp,
                        dwr, temp, top_k, top_p, seeds)
                else:
                    dfn = self._draft_decoder.draft_fn(g)
                    d, self._draft_caches = dfn(
                        self._draft_params, self._draft_caches,
                        tokens, pos, self._draft_table.copy(), dwp,
                        dwr)
        except BaseException as e:  # noqa: BLE001 — draft-side only:
            # the TARGET caches were never touched, so no stream fails;
            # demote and decode this boundary plain
            self._spec_demote("draft_error", e)
            self._decode_once(False, "speculation")
            return
        t1 = self.clock()
        if traced:
            self._tracer.span("decode_step", None, t0, t1,
                              tid=self.name or "generate",
                              step=self._n_steps, phase="draft",
                              gamma=g, active=nactive,
                              program=_program_name(dfn))
        # verify failures propagate to the caller's containment: the
        # donated target caches are poisoned, so _recover_from_
        # dispatch_error must fail the streams and rebuild everything
        vfn = self._decoder.verify_fn(g, sampled=sampled)
        with jax.profiler.StepTraceAnnotation(
                "generate", step_num=self._n_steps):
            with self._phase("generate.dispatch"):
                if sampled:
                    (n_acc, out), self._caches = vfn(
                        self._params, self._caches, tokens, d, q,
                        pos, self._table.copy(), vwp, vwr, temp, top_k,
                        top_p, seeds)
                else:
                    (n_acc, out), self._caches = vfn(
                        self._params, self._caches, tokens, d, pos,
                        self._table.copy(), vwp, vwr)
            # THE one host sync per round for the whole batch (RL010):
            # accept counts + the emit-ready token rows together
            with self._phase("generate.fetch"):
                n_host, out_host = jax.device_get((n_acc, out))
        n_host = np.asarray(n_host)
        out_host = np.asarray(out_host)
        now = self.clock()
        self._n_steps += 1
        with self._phase("generate.deliver"):
            emitted = proposed = accepted = 0
            for i, s in active:
                n = int(n_host[i])
                proposed += g
                accepted += n
                # rows < n are the accepted proposals; row n (when < γ) is
                # the verifier's correction — emit in order, stopping
                # EXACTLY where the sequential engine stops (EOS /
                # max_new can land mid-window)
                for t in range(min(n + 1, g)):
                    tok = int(out_host[i, t])
                    s.length += 1
                    s.generated += 1
                    s.last_token = tok
                    s.stream._emit(tok)
                    emitted += 1
                    if s.generated >= s.stream.max_new or (
                            self.eos_id is not None
                            and tok == self.eos_id):
                        break
                self._trim_slot_pages(i, s)
                self._retire(i, s, s.generated >= s.stream.max_new, now)
            if traced:
                self._tracer.span("decode_step", None, t1, now,
                                  tid=self.name or "generate",
                                  step=self._n_steps - 1, phase="verify",
                                  gamma=g, active=nactive,
                                  proposed=proposed, accepted=accepted,
                                  program=_program_name(vfn))
            self.metrics.record_spec_round(proposed, accepted)
            # TPOT percentiles become per-ROUND walls here (documented in
            # GenerationMetrics.snapshot); tokens_per_s stays comparable
            self.metrics.record_decode_step(emitted, now - t0)
            self._spec_account(g, proposed, accepted, now - t0)
            if self._gen_faults:
                self._fire_cancel_at_token(
                    [(i, s, s.generated >= s.stream.max_new)
                     for i, s in active], now)
            if self.stats_every and self._n_steps % self.stats_every == 0:
                self.metrics.emit(extra={"slots": self.slots,
                                         "active": nactive})
        self._open_turn()

    def _ensure_draft_pages(self, slot: int, st: _Slot,
                            upto_pos: int) -> bool:
        """Grow the slot's DRAFT page table to cover positions
        ``[0, upto_pos)`` — same geometry as the target's, but no
        prefix sharing (draft rows are never promoted to the trie) and
        so no eviction pressure valve."""
        need = (int(upto_pos) - 1) // self.page_size + 1
        while len(st.draft_pages) < need:
            pg = self._draft_pool.alloc()
            if pg is None:
                return False
            self._draft_table[slot, len(st.draft_pages)] = pg
            st.draft_pages.append(pg)
        return True

    def _trim_slot_pages(self, slot: int, st: _Slot) -> None:
        """Release the trailing pages a partially-accepted window
        provisioned past the accept point, in BOTH pools — the
        page-granular rollback (rejected rows inside kept pages need no
        rollback at all: the causal mask hides them until the next
        round overwrites them).  Released target pages sit strictly
        after the shared prompt prefix (length >= prompt.size), so
        their refcount is 1 and they return to the pool for real."""
        keep = st.length // self.page_size + 1
        while len(st.pages) > keep:
            pg = st.pages.pop()
            self._table[slot, len(st.pages)] = self._pool.no_page
            self._pool.release(pg)
        while len(st.draft_pages) > keep:
            pg = st.draft_pages.pop()
            self._draft_table[slot, len(st.draft_pages)] = \
                self._draft_pool.no_page
            self._draft_pool.release(pg)

    def _draft_prefill(self, slot: int, st: _Slot) -> None:
        """Mirror a freshly-joined stream's prompt into the DRAFT cache
        with ONE monolithic prefill dispatch (no chunking, no prefix
        sharing — draft rows are private, and the draft is a fraction
        of the target so one chunk is cheap).  No host sync: the
        draft's own next-token argmax is unused — round 0 scans from
        the TARGET's real first token.  Any draft-side failure demotes
        speculation; the stream itself is untouched."""
        prompt = st.prompt
        size = int(prompt.size)
        if st.generated >= st.stream.max_new or (
                self.eos_id is not None
                and st.last_token == self.eos_id):
            return  # retiring at this boundary: no draft rows needed
        try:
            if not self._ensure_draft_pages(slot, st, size):
                raise KVCacheExhausted(
                    f"no draft KV page free for a {size}-token prompt "
                    f"({self._draft_pool.pages_in_use} of "
                    f"{self.num_pages} in use)")
            bucket = self._draft_decoder.prefill_bucket(size)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :size] = prompt
            fn = self._draft_decoder.prefill_fn(bucket)
            t0 = self.clock()
            with jax.profiler.StepTraceAnnotation(
                    "gen-draft-prefill", step_num=self._n_steps):
                _, self._draft_caches = fn(
                    self._draft_params, self._draft_caches,
                    tokens, self._draft_table[slot].copy(),
                    np.int32(slot), np.int32(0), np.int32(size))
            if self._tracer.active and st.stream.trace is not None:
                self._tracer.span("prefill", st.stream.trace, t0,
                                  self.clock(),
                                  tid=self.name or "generate",
                                  slot=slot, phase="draft",
                                  prompt_len=size)
        except BaseException as e:  # noqa: BLE001 — draft-side only:
            # demote and keep serving plain; the target stream already
            # has its first token
            self._spec_demote("draft_prefill_error", e)

    def _spec_account(self, g: int, proposed: int, accepted: int,
                      wall: float) -> None:
        """Post-round controller bookkeeping: accept-rate EWMA, per-γ
        round-cost EWMA, the accept-collapse guard, and (adaptive
        policy) the periodic γ re-pricing."""
        self._spec_rounds += 1
        self._spec_seen_proposed += proposed
        a = self._SPEC_EWMA_ALPHA
        if proposed:
            rate = accepted / proposed
            self._accept_ewma = (
                rate if self._accept_ewma is None
                else (1 - a) * self._accept_ewma + a * rate)
        prev = self._spec_costs.get(g)
        self._spec_costs[g] = (wall if prev is None
                               else (1 - a) * prev + a * wall)
        if (self._spec_seen_proposed >= self._SPEC_COLLAPSE_MIN_PROPOSED
                and self._accept_ewma is not None
                and self._accept_ewma < self._SPEC_COLLAPSE_ACCEPT):
            # a useless draft burns a dispatch per round for ~nothing —
            # the engine is FASTER without it
            self._spec_demote("accept_collapse", None)
            return
        if (self.spec_policy == "adaptive"
                and len(self._spec_candidates) > 1
                and self._spec_rounds % self._SPEC_RETUNE_EVERY == 0):
            self._spec_gamma = self._spec_retune()

    def _spec_retune(self) -> int:
        """Price each candidate γ with the live accept-rate EWMA α and
        its calibrated round-wall EWMA (warmup-seeded, live-updated):
        expected emitted tokens per round is ``(1 - α^γ) / (1 - α)``
        (accepted prefix + correction, no bonus token), so the winner
        maximizes that over its cost — the gen_stats feedback loop
        pricing depth like the SOAP cost model prices strategies."""
        alpha = self._accept_ewma if self._accept_ewma is not None \
            else 0.5
        alpha = min(0.999, max(0.001, alpha))
        best, best_rate = self._spec_gamma, -1.0
        for g in self._spec_candidates:
            cost = self._spec_costs.get(g)
            if not cost or cost <= 0:
                continue
            exp_tokens = (1.0 - alpha ** g) / (1.0 - alpha)
            rate = exp_tokens / cost
            if rate > best_rate:
                best, best_rate = g, rate
        return best

    def _spec_demote(self, reason: str, exc) -> None:
        """Demote to plain decode for the rest of the engine's
        lifetime: drop the draft pool/table/caches (their HBM frees),
        count the fallback, emit ONE serve_health event.  NO stream
        fails — the target's state is untouched; every active stream
        keeps generating plain from exactly where it is."""
        if not self._spec_on:
            return
        self._spec_on = False
        self._spec_gamma = 0
        self._draft_caches = None
        self._draft_pool = None
        self._draft_table = None
        self.draft_kv_cache_bytes = 0
        for s in self._slots_state:
            if s is not None:
                s.draft_pages = []
        self.metrics.record_spec_fallback()
        get_logger("serve").event(
            "serve_health", model=self.name, component="speculation",
            status="fallback", reason=reason,
            error=("" if exc is None
                   else f"{type(exc).__name__}: {exc}"[:300]),
            step=self._n_steps,
            accept_ewma=(round(self._accept_ewma, 4)
                         if self._accept_ewma is not None else None))

    def _spec_stats(self) -> Dict:
        """The live speculation view merged into gen_stats/stats():
        off (no draft configured) / on / fallback (demoted)."""
        state = ("off" if self.draft_model is None
                 else ("on" if self._spec_on else "fallback"))
        return {
            "spec": state,
            "spec_gamma": self._spec_gamma,
            "spec_policy": self.spec_policy,
            "draft_kv_cache_bytes": self.draft_kv_cache_bytes,
        }

    def _recover_from_dispatch_error(self, e: BaseException,
                                     event: str) -> None:
        """A failed prefill/decode dispatch raised AFTER the cache
        pytree was donated: off-CPU the pool buffers are invalidated,
        so every active stream's state — and every cached prefix page
        — is unrecoverable.  Fail them all, rebuild the pool + prefix
        cache (lifetime counters carry over), reallocate the device
        pools, and keep serving queued prompts (the engine recovers; a
        poisoned dispatch must never wedge it on 'Array has been
        deleted' forever).  With a step in flight the streams of BOTH
        steps fail, each once: a slot that retired by count still owes
        its stream the tokens that never came."""
        failed = 0
        now = self.clock()
        for s in self._unsettled():
            if s.stream._fail(e):
                self.metrics.record_failure(e)
                self._trace_terminal(s.stream, "error", now)
                failed += 1
            s.done = True
        self._slots_state = [None] * self.slots
        self._prefill_q.clear()
        # the pipeline is empty again: what was in flight came out of,
        # or went into, the cache the failed program consumed
        self._inflight, self._cur, self._landing = None, _Flight(), []
        self._prev_tokens = self._prev_first = None
        if self._prefix is not None:
            self._evictions_base += self._prefix.evictions
            self._evict_scanned_base += self._prefix.evict_scanned
        self._pool_high_base = max(self._pool_high_base,
                                   self._pool.high_water)
        self._pool = KVPagePool(self.num_pages, self.page_size)
        self._prefix = (PrefixCache(self._pool)
                        if self.prefix_cache_enabled else None)
        self._table = np.full((self.slots,
                               self._decoder.pages_per_slot),
                              self._pool.no_page, np.int32)
        self._caches = self._decoder.init_cache()
        if self._spec_on:
            # the draft's pool/table/caches are re-armed with the
            # target's: the failed round may have donated either side,
            # and the slots they described are gone regardless
            self._draft_pool = KVPagePool(self.num_pages,
                                          self.page_size)
            self._draft_table = np.full(
                (self.slots, self._draft_decoder.pages_per_slot),
                self._draft_pool.no_page, np.int32)
            self._draft_caches = self._draft_decoder.init_cache()
        get_logger("serve").event(  # RL011-ok: gen_decode_error |
            # gen_prefill_error, both declared in obs/events.py —
            # callers pass the literal
            event, model=self.name, step=self._n_steps,
            error=f"{type(e).__name__}: {e}"[:300],
            failed_streams=failed)
        # generation's dispatch-error flight trigger (no-op unless
        # FF_FLIGHT_DIR is set)
        flight_dump(event, extra={"model": self.name,
                                  "step": self._n_steps,
                                  "error": f"{type(e).__name__}: {e}"[:300],
                                  "failed_streams": failed})

    def _unsettled(self) -> List[_Slot]:
        """Every slot state whose stream has not been handed its end:
        the occupied slots, and the streams a boundary still in flight
        (or being built) owes tokens to — a slot that retired by count
        is only there."""
        out = [s for s in self._slots_state if s is not None]
        for f in (*self._landing, self._inflight, self._cur):
            if f is None:
                continue
            out.extend(s for _, s, _ in f.rows)
            if f.join is not None:
                out.append(f.join[1])
        return list({id(s): s for s in out if not s.done}.values())

    def _retire(self, slot: int, s: _Slot, last: bool,
                now: float) -> None:
        """After a token was handed to ``s``'s stream: end the stream if
        it was cancelled, if the token was its ``last`` (decided by
        count when the token's program was dispatched) or the EOS, and
        free the slot — and its pages — where the count has not already
        done so.  Run at every hand-over, so a mid-generation cancel
        frees KV capacity for the next queued prompt at once; what is
        still in flight for an ended stream is dropped when it lands."""
        if s.stream.cancelled:
            exc = GenerationCancelled(
                f"stream cancelled after {len(s.stream._tokens)} "
                f"token(s); KV slot {slot} and {len(s.pages)} page(s) "
                f"freed")
            self._fail_slot(slot, s, exc, "cancelled")
            return
        if last or (self.eos_id is not None
                    and s.last_token == self.eos_id):
            if s.stream._finish():
                self.metrics.record_request(now - s.stream.t_submit,
                                            deadlined=s.stream.deadlined)
                self._trace_terminal(s.stream, "completed", now)
            self._settle(slot, s)

    def _abort_active(self) -> None:
        """drain(timeout) expired: hand over what already lies on the
        device, then shed whatever is still decoding or prefilling
        (pages go back to the pool with the slots)."""
        try:
            self._drain("abort")
        except BaseException as e:  # noqa: BLE001 — what was in flight
            # fails as a dispatch error; nothing is left to shed then
            self._recover_from_dispatch_error(e, "gen_decode_error")
        now = self.clock()
        exc = SheddedError(
            "engine drained mid-generation (drain timeout)")
        for s in self._unsettled():
            if s.stream._fail(exc):
                self.metrics.record_failure(exc)
                self._trace_terminal(s.stream, "shed", now)
            s.done = True
        for i, s in enumerate(self._slots_state):
            if s is not None:
                self._release_slot(i, s)
        self._inflight, self._cur, self._landing = None, _Flight(), []
        self._prefill_q.clear()
        while self._adopt_q:
            try:
                payload = self._adopt_q.popleft()
            except IndexError:
                break
            exc = SheddedError(
                "engine drained before adopting a migrated stream")
            if payload["stream"]._fail(exc):
                self.metrics.record_failure(exc)
                self._trace_terminal(payload["stream"], "shed", now)

    # ---- fault injection (FF_FAULT generation kinds) -------------------
    def _fire_slow_decode(self) -> None:
        for st in self._gen_faults:
            if st["kind"] == "serve_slow_decode" and st["fired"] < st["n"]:
                st["fired"] += 1
                self._sleep(st["ms"] / 1e3)

    def _fire_spec_draft_fail(self) -> None:
        """``FF_FAULT=spec_draft_fail:N`` — the Nth draft dispatch
        raises (once), exercising the demote-to-plain-decode path: the
        serve_health fallback event fires and NO stream fails."""
        for st in self._gen_faults:
            if st["kind"] == "spec_draft_fail" and not st["fired"] \
                    and self._spec_rounds + 1 >= st["n"]:
                st["fired"] = 1
                raise RuntimeError(
                    f"FF_FAULT spec_draft_fail: injected draft "
                    f"failure at round {self._spec_rounds + 1}")

    def _fire_cancel_at_token(self, rows, now: float) -> None:
        """``FF_FAULT=serve_cancel_at_token:N`` — the first stream among
        the ``rows`` just handed their tokens that holds N of them is
        cancelled (once)."""
        for st in self._gen_faults:
            if st["kind"] != "serve_cancel_at_token" or st["fired"]:
                continue
            for i, s, last in rows:
                if not s.done and len(s.stream._tokens) >= st["n"]:
                    st["fired"] = 1
                    get_logger("serve").event(
                        "gen_fault_cancel", model=self.name, slot=i,
                        generated=len(s.stream._tokens),
                        at_token=st["n"])
                    s.stream.cancel()
                    self._retire(i, s, last, now)
                    break

    # ---- strategy-sharded construction ---------------------------------
    @classmethod
    def from_strategy(cls, model, strategy_file: str, mesh=None,
                      **kwargs) -> "GenerationEngine":
        """Build a tensor-parallel generation engine from a searched
        strategy ``.pb``: load the per-op ParallelConfigs, compile the
        model against them (ffcheck-verified, mesh inferred from the
        strategy when not given), place/re-place every parameter under
        its strategy PartitionSpec, and shard the KV page pools' head
        dim over the ``c`` axis — one checkpoint, any searched
        sharding.

        Accepts a fresh (uncompiled) model — compiled+initialized here
        — or an already-initialized one, whose live params are gathered
        and re-placed (the reshard pattern)."""
        from ...strategy.proto import load_strategy_file
        strategies = load_strategy_file(strategy_file)
        model.config.strategies.update(strategies)
        if not model._compiled:
            model.compile(mesh=mesh)
            model.init_layers(seed=model.config.seed)
        else:
            for op in model.layers:
                op.parallel_config = model.config.strategies.get(
                    op.name, op.parallel_config)
            if mesh is not None:
                model.mesh = mesh
            else:
                # the strategy names its own mesh (the same inference
                # compile() runs): rebuild when the live one differs
                from ...parallel.mesh import MachineMesh
                shape = model._infer_mesh_shape()
                if (model.mesh is None
                        or {a: s for a, s in model.mesh.sizes.items()
                            if s > 1} != {a: s for a, s in shape.items()
                                          if s > 1}):
                    model.mesh = MachineMesh(shape)
            # re-place live params under the strategy's shardings (the
            # partition-rule -> PartitionSpec pytree pattern); the AOT
            # forward cache lowered for the old placement must drop —
            # and so must any cached GraphDecoders, whose pool layout
            # was derived from the OLD mesh
            for p in model.parameters:
                if p.name in model._params:
                    val = model._gather_host(model._params[p.name])
                    model._params[p.name] = model._placed_param(p, val)
            model._fwd_compiled.clear()
            model._exec_digest_cache = None
            model.__dict__.pop("_gen_decoders", None)
            model._build_step_fns()
        return cls(model, **kwargs)


def _load_gen_faults() -> List[Dict]:
    """Materialize the FF_FAULT generation specs into per-engine firing
    state (start() calls this once per engine)."""
    out: List[Dict] = []
    for spec in faults.generation_faults():
        out.append({
            "kind": spec.kind,
            "n": int(spec.arg),
            "ms": float(spec.extras.get("ms", "50")),
            "fired": 0,
        })
    return out
