"""ServingEngine — shape-bucketed AOT executables + dynamic
micro-batching over a compiled FFModel (docs/serving.md).

The training side amortizes host cost with fused multi-step dispatch
(PR 4); this is the inference analogue for a request-serving loop, in
the spirit of TVM's ahead-of-time specialized executables applied to
serving: compile ONCE per shape bucket at startup
(``jax.jit(...).lower(...).compile()`` via
:meth:`FFModel.forward_compiled`, warmed through the persistent compile
cache), then keep the device saturated with dynamically packed
micro-batches.  Per dispatch the engine pays exactly one device
execution and one ``jax.device_get`` for the whole packed batch — no
per-request host sync (repo_lint RL005 locks the scatter loop down the
same way RL004 locks fit/evaluate/predict).

Threading model: any number of producer threads call :meth:`submit`
(returns a ``concurrent.futures.Future``); ONE dispatcher thread owns
all jax work — it pulls coalesced batches from the
:class:`~flexflow_tpu.serving.batcher.MicroBatcher`, packs them into
the smallest covering bucket, runs the bucket executable with the
model's device-pinned params (passed per call, never donated, never
re-pinned), fetches once, and scatters per-request row slices back to
the futures.

Overload is a handled regime (docs/serving.md "Overload, SLOs &
degradation"): the queue is bounded with block/reject/shed_oldest
admission, requests carry deadlines (expired BEFORE packing — no dead
dispatches) and priority classes, the engine walks a health state
machine (``starting → serving → degraded → draining → stopped``) with
a bounded :meth:`drain`, and the ``serve_slow_dispatch`` /
``serve_fail_dispatch`` / ``serve_queue_spike`` FF_FAULT kinds inject
the whole overload matrix deterministically (injectable clock + sleep,
:mod:`flexflow_tpu.faults`).
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import faults
from ..compile_cache import enable as _enable_compile_cache
from ..fflogger import get_logger
from ..obs import lockwatch
from ..obs.flight import flight_dump, get_flight
from ..obs.trace import phase_of, tracer_from_config
from .batcher import (ADMISSION_POLICIES, MicroBatcher, Request, bucket_for,
                      derive_buckets, split_sizes)
from .errors import OverloadError, SheddedError
from .metrics import ServingMetrics

HEALTH_STATES = ("starting", "serving", "degraded", "draining", "stopped")


def _resolve_future(fut: Future, out) -> bool:
    """Complete ``fut`` with a result or exception, tolerating client
    interference: ``set_running_or_notify_cancel()`` atomically claims
    a pending future (after which a client ``cancel()`` can no longer
    race the ``set_result``) and reports a future the client already
    cancelled, which the engine simply drops — a cancelled or
    double-completed future must never raise on the dispatcher thread
    (an escaped InvalidStateError would kill the dispatcher and hang
    every subsequent request).  Returns True when ``fut`` was actually
    completed here."""
    try:
        if not fut.set_running_or_notify_cancel():
            return False  # client cancelled while queued
    except (RuntimeError, InvalidStateError):
        return False  # already completed (e.g. the error path revisiting)
    if isinstance(out, BaseException):
        fut.set_exception(out)
    else:
        fut.set_result(out)
    return True


class _Join:
    """Reassembles an oversize request that was split into chunks at
    submit: chunk outputs land by index (the single dispatcher thread
    completes them in FIFO order, but indexing is order-free anyway)
    and the logical future resolves once — with the concatenated rows —
    when the last chunk arrives.  On the error/expiry path the FIRST
    failing chunk resolves the future; the surviving queued siblings
    turn stale (``future.done()``) and the batcher drops them before
    packing, which is what makes split-request expiry atomic: the
    logical request fails once and no orphan chunk burns a dispatch."""

    def __init__(self, future: Future, nparts: int, t_submit: float,
                 metrics: ServingMetrics, deadlined: bool = False,
                 trace_done: Optional[Callable] = None):
        self.future = future
        self.parts: list = [None] * nparts
        self.missing = nparts
        self.t_submit = t_submit
        self.metrics = metrics
        self.deadlined = deadlined
        # trace_done(phase, now): records the logical request's ONE
        # terminal span (None when the request was not sampled)
        self.trace_done = trace_done
        self.lock = lockwatch.lock("_Join.lock")

    def part(self, i: int) -> Callable:
        def on_done(out, now: float) -> bool:
            return self._complete(i, out, now)
        return on_done

    def _complete(self, i: int, out, now: float) -> bool:
        """Returns True iff THIS call completed the logical future —
        the error path counts failed logical requests from it, so a
        split request failing across several packed batches is counted
        once, matching the population every other metric uses."""
        with self.lock:
            if self.future.done():
                return False
            if isinstance(out, BaseException):
                pass  # resolve OUTSIDE the lock, below
            else:
                self.parts[i] = out
                self.missing -= 1
                if self.missing:
                    return False
        # resolution (and the metrics/trace callbacks it triggers —
        # done-callbacks run synchronously inside set_result/exception)
        # happens outside _Join.lock: callbacks may take other locks,
        # and _resolve_future's first-writer-wins keeps the
        # counted-once invariant without holding ours
        if isinstance(out, BaseException):
            if _resolve_future(self.future, out):
                self.metrics.record_failure(out)
                if self.trace_done is not None:
                    self.trace_done(phase_of(out), now)
                return True
            return False
        if _resolve_future(self.future,
                           np.concatenate(self.parts, axis=0)):
            self.metrics.record_request(now - self.t_submit,
                                        deadlined=self.deadlined)
            if self.trace_done is not None:
                self.trace_done("completed", now)
            return True
        return False


class ServingEngine:
    """Inference engine over a compiled+initialized :class:`FFModel`.

    ::

        engine = ServingEngine(model)          # AOT-compiles all buckets
        with engine:                           # starts the dispatcher
            fut = engine.submit(x_rows)        # (n, ...) rows, n >= 1
            y = fut.result()                   # (n, num_classes)

    Knobs resolve from ``model.config`` (CLI ``--serve-max-batch``,
    ``--serve-max-wait-ms``, ``--serve-buckets``, ``--serve-max-queue-
    rows``, ``--serve-admission``, ``--serve-starvation-ms``) unless
    overridden by constructor arguments; ``clock`` and ``sleep`` are
    injectable for deterministic tests (``sleep`` is only ever used by
    the ``serve_slow_dispatch`` fault)."""

    def __init__(self, model, max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 buckets: Optional[str] = None, stats_every: int = 64,
                 metrics_window_s: float = 30.0,
                 max_queue_rows: Optional[int] = None,
                 admission: Optional[str] = None,
                 starvation_ms: Optional[float] = None,
                 degraded_after_errors: int = 2,
                 degraded_drop_frac: float = 0.5,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 name: str = ""):
        assert model._compiled, "compile() + init_layers() the model first"
        # persistent compile cache: bucket warmup below is exactly the
        # compile-once-at-startup cost the cache makes warm across
        # process restarts (idempotent; defers to a harness-picked dir)
        _enable_compile_cache()
        cfg = model.config
        self.model = model
        self.max_batch = int(max_batch or cfg.serve_max_batch
                             or cfg.batch_size)
        self.max_wait_ms = float(
            cfg.serve_max_wait_ms if max_wait_ms is None else max_wait_ms)
        self.buckets: Tuple[int, ...] = derive_buckets(
            self.max_batch, cfg.serve_buckets if buckets is None else buckets)
        self.max_queue_rows = int(
            cfg.serve_max_queue_rows if max_queue_rows is None
            else max_queue_rows)
        self.admission = (cfg.serve_admission if admission is None
                          else admission)
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown serve_admission {self.admission!r} (want one "
                f"of {', '.join(ADMISSION_POLICIES)})")
        self.clock = clock
        self._sleep = sleep
        self.stats_every = int(stats_every)
        self._batcher = MicroBatcher(
            self.max_batch, self.max_wait_ms, clock=clock,
            max_queue_rows=self.max_queue_rows, admission=self.admission,
            starvation_ms=float(cfg.serve_starvation_ms
                                if starvation_ms is None else starvation_ms))
        # tenant identity: stamped on serve_stats/serve_health/
        # serve_dispatch_error events so N co-resident engines emit
        # distinguishable streams (FleetEngine passes the registry
        # name; "" = untagged single-engine default, overridable via
        # FFConfig.serve_model_name / --serve-model-name)
        self.name = str(name or cfg.serve_model_name)
        self.metrics = ServingMetrics(
            window_s=metrics_window_s, clock=clock,
            queue_depth_fn=lambda: self._batcher.queue_depth,
            model=self.name)
        # observability plane (docs/observability.md): the tracer's
        # `active` bool is the ONE lock-free check the dispatch hot
        # path reads when tracing is off; get_flight() installs the
        # passive event/span taps so a post-mortem dump covers this
        # engine's whole lifetime
        self._tracer = tracer_from_config(cfg)
        get_flight()
        self._n_inputs = len(model.input_tensors)
        self._in_dtypes = [t.dtype for t in model.input_tensors]
        self._in_shapes = [tuple(t.shape[1:]) for t in model.input_tensors]
        # int8 weight-only quantization (docs/serving.md): applied at
        # engine WARMUP so the bucket executables below lower against
        # the quantized params, with the symmetric-rounding quality
        # bound checked before anything serves — a violating table
        # means the quantizer is broken, and refusing to start beats
        # silently serving garbage
        self.quantize = str(getattr(cfg, "serve_quantize", "") or "")
        if self.quantize:
            qrep = model.quantize_weights(self.quantize)
            if not qrep["bound_ok"]:
                raise RuntimeError(
                    f"int8 quantization quality bound violated at "
                    f"warmup: max_abs_err {qrep['max_abs_err']:.3e} > "
                    f"bound {qrep['error_bound']:.3e} "
                    f"({len(qrep['weights'])} weight(s)); refusing to "
                    f"serve")
        # pay every bucket's AOT compile up front; the executables live
        # in model._fwd_compiled (the same cache predict() uses, so a
        # model re-compile() is followed, never served stale) — the
        # engine deliberately keeps no snapshot of its own
        for b in self.buckets:
            model.forward_compiled(b)
        # bucket warmup traced the forward: surface any replicate
        # fallbacks NOW — a serving-only process must see its FF106s
        # without ever running a train step (ISSUE 9)
        model._surface_runtime_fallbacks()
        # lifecycle state machine: every write happens under
        # self._lifecycle (RL009); the lock-free health property reads
        # are the one documented exception
        self._thread: Optional[  # guarded_by: self._lifecycle
            threading.Thread] = None
        # fleet mode: a FleetEngine drives dispatch_pending() instead
        # of this engine owning a thread (serving/fleet)
        self._external = False   # guarded_by: self._lifecycle
        self._n_dispatch = 0  # dispatcher-thread-only (single writer)
        self._stopped = False    # guarded_by: self._lifecycle
        self._draining = False   # guarded_by: self._lifecycle
        self._consec_errors = 0  # dispatcher-thread-only (single writer)
        self._degraded_after_errors = int(degraded_after_errors)
        self._degraded_drop_frac = float(degraded_drop_frac)
        self._last_health = "starting"  # guarded_by: self._health_lock
        self._health_lock = lockwatch.lock("ServingEngine._health_lock")
        # final serve_stats emitted exactly once
        self._finalized = False  # guarded_by: self._lifecycle
        self._shutdown_done = threading.Event()
        self._serve_faults: List[Dict] = []
        self._lifecycle = lockwatch.lock("ServingEngine._lifecycle")

    # ---- health state machine ------------------------------------------
    @property
    def health(self) -> str:
        """Engine lifecycle/health state: ``starting`` (constructed,
        dispatcher not running), ``serving``, ``degraded`` (consecutive
        dispatch errors or windowed shed+reject rate over threshold —
        still serving what it can), ``draining`` (drain() in progress:
        no admissions, queue flushing) or ``stopped``.  Computed from
        live counters, so a recovery — successful dispatch, drop rate
        decaying out of the window — flips it back without an edge
        event having to fire first."""
        if self._stopped:      # unguarded-ok: lock-free health read
            return "stopped"
        if self._draining:     # unguarded-ok: lock-free health read
            return "draining"
        if (self._thread is None  # unguarded-ok: lock-free health read
                and not self._external):  # unguarded-ok: lock-free read
            return "starting"
        if self._consec_errors >= self._degraded_after_errors:
            return "degraded"
        rate, submitted = self.metrics.drop_stats()
        if submitted >= 4 and rate >= self._degraded_drop_frac:
            return "degraded"
        return "serving"

    def _health_tick(self) -> None:
        """Emit a structured ``serve_health`` event on state edges —
        the pull-side `health` property is always live, but a
        transition must also be visible in the event stream.  The
        compare-and-set on ``_last_health`` is locked: ticks fire from
        producer threads (reject paths) AND the dispatcher, and an
        unsynchronized read-modify-write would duplicate or swallow
        edges in the event stream."""
        with self._health_lock:
            # state is computed INSIDE the lock and the event emitted
            # before releasing it: a tick that computed its state
            # earlier but committed later would write a reversed edge
            # into both _last_health and the event stream
            state = self.health
            prev = self._last_health
            if state == prev:
                return
            self._last_health = state
            rate, submitted = self.metrics.drop_stats()
            get_logger("serve").event(
                "serve_health", model=self.name, prev=prev, state=state,
                consec_errors=self._consec_errors,
                drop_rate=round(rate, 4), window_submitted=submitted,
                queue_depth=self._batcher.queue_depth)
        if state == "degraded":
            # a health edge INTO degraded is a flight-recorder trigger
            # (docs/observability.md): the ring holds the events/spans
            # that led here.  Outside the health lock — dump I/O must
            # never serialize health ticks.
            flight_dump("health_degraded",
                        extra={"model": self.name, "prev": prev,
                               "drop_rate": round(rate, 4)})

    # ---- lifecycle -----------------------------------------------------
    def start(self) -> "ServingEngine":
        with self._lifecycle:
            if self._stopped:
                # the batcher closed irreversibly at stop(); a
                # restarted dispatcher would exit instantly while
                # submit() raised — fail loudly instead of appearing
                # to serve
                raise RuntimeError(
                    "engine was stopped; create a new ServingEngine "
                    "(the AOT bucket executables are cached on the "
                    "model, so a fresh engine starts warm)")
            if self._thread is None:
                self._serve_faults = _load_serve_faults()
                self._thread = threading.Thread(
                    target=self._dispatch_loop, name="ff-serve-dispatch",
                    daemon=True)
                self._thread.start()
        self._health_tick()
        return self

    def stop(self) -> None:
        """Drain pending requests fully (unbounded), stop the
        dispatcher, emit final stats.  Idempotent and safe under
        concurrent callers — the lifecycle lock serializes them, every
        stop() returns only once the drain finished, and only the
        first emits the final snapshot (the dispatcher thread never
        takes this lock, so holding it across the join cannot
        deadlock).  The engine is single-use — see start().  For a
        BOUNDED drain that fails stragglers instead of waiting them
        out, see :meth:`drain`."""
        to_fail: List[Request] = []
        err = now = None
        with self._lifecycle:
            self._stopped = True
            self._batcher.close()
            if self._thread is not None:
                # lock-ok: dispatcher never takes _lifecycle, so joining
                # it under the lock cannot deadlock (see docstring)
                self._thread.join()
                self._thread = None
                if not self._finalized:
                    # exactly one final snapshot, even when stop() and
                    # drain() race — whichever joins first emits
                    self._finalized = True
                    self.metrics.emit(extra={"final": True,
                                             "max_batch": self.max_batch,
                                             "health": "stopped"})
            else:
                # no dispatcher thread (never started, or fleet-managed):
                # nothing will drain the queue, so fail any futures
                # still queued — leaving them pending would block
                # result() forever.  SheddedError, like drain()'s
                # stragglers: a shutdown eviction is load management,
                # and the typed contract (`except ServingError`) must
                # cover it
                now = self.clock()
                err = SheddedError(
                    "engine stopped with work still queued (fleet "
                    "unload)" if self._external
                    else "engine stopped before it was started")
                while True:
                    reqs = self._batcher.poll()
                    if not reqs:
                        break
                    to_fail.extend(reqs)
        # fail the evicted requests OUTSIDE _lifecycle: on_done
        # resolves futures, and their done-callbacks take _Join /
        # metrics / tracer locks the static lock graph cannot see
        # through a stored callable
        for r in to_fail:
            r.on_done(err, now)
        self._health_tick()
        # retire the live registry hooks: a stopped engine must not be
        # retained by the process-global registry (fleet swaps —
        # counters stay readable, the gauge provider drops)
        self.metrics.release()
        self._shutdown_done.set()

    def drain(self, timeout: Optional[float] = None) -> Dict:
        """Graceful shutdown verb: stop admitting (subsequent
        ``submit`` raises), flush what is queued, and after ``timeout``
        seconds fail the stragglers with :class:`SheddedError` instead
        of waiting for them (None = wait forever, like stop()).
        Returns the final stats snapshot.  Idempotent; the engine is
        stopped afterwards (single-use, like stop())."""
        with self._lifecycle:
            # _draining gates concurrent drain()/drain(): only the
            # first caller runs the shutdown (stop() racing in is
            # handled by the _finalized emit-once guard)
            already = self._stopped or self._draining
            thread = self._thread
            if not already:
                self._draining = True
                self._batcher.close()
        if already:
            # a concurrent first drain()/stop() is still shutting
            # down: wait it out, so every drain() returns only once
            # the engine really is stopped (the documented
            # postcondition — callers tear down shared state next)
            self._shutdown_done.wait()
            return self.stats()
        self._health_tick()
        get_logger("serve").event(
            "serve_drain", model=self.name, timeout_s=timeout,
            queue_depth=self._batcher.queue_depth,
            pending_rows=self._batcher.pending_rows)
        shed = 0
        if thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                # dispatcher still busy past the budget: pull the
                # remaining queue out from under it and fail those
                # futures fast — the in-flight batch still completes
                stragglers = self._batcher.fail_pending()
                now = self.clock()
                for r in stragglers:
                    if r.on_done(SheddedError(
                            f"engine drained with work still queued "
                            f"(drain timeout {timeout}s)"), now):
                        shed += 1
                # bounded SECOND join too: a dispatcher wedged inside a
                # device call (the unhealthy case drain exists for)
                # must not hang the shutdown path — give the in-flight
                # dispatch one more budget, then abandon the daemon
                # thread and finish shutting down
                thread.join(timeout)
                if thread.is_alive():
                    get_logger("serve").event(
                        "serve_drain_abandoned",
                        model=self.name,
                        timeout_s=timeout,
                        note="dispatcher wedged in an in-flight "
                             "dispatch; daemon thread abandoned")
        else:
            now = self.clock()
            for r in self._batcher.fail_pending():
                if r.on_done(SheddedError(
                        "engine drained before it was started"), now):
                    shed += 1
        with self._lifecycle:
            # _stopped BEFORE clearing _draining: the lock-free health
            # property must never observe the (not stopped, not
            # draining) gap and report a shut-down engine as 'serving'
            self._stopped = True
            self._draining = False
            self._thread = None
            first = not self._finalized
            self._finalized = True
        self._health_tick()
        snap = self.stats()
        if first:
            self.metrics.emit(extra={"final": True,
                                     "max_batch": self.max_batch,
                                     "health": "stopped",
                                     "drain_shed": shed})
        self.metrics.release()
        self._shutdown_done.set()
        return snap

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- producer side -------------------------------------------------
    def submit(self, *xs, deadline_ms: Optional[float] = None,
               priority: int = 0) -> Future:
        """Queue one inference request of ``n`` rows (each positional
        arg is one model input, leading dim ``n``) and return a Future
        resolving to the ``(n, ...)`` output rows.  Thread-safe.
        Requests larger than ``max_batch`` are split into chunks and
        transparently reassembled.

        ``deadline_ms`` (from submit time): if the request is still
        queued when it passes, the batcher expires it before packing
        and the future fails with :class:`DeadlineExceeded` — no device
        dispatch is burned.  ``priority`` (int, higher = served first)
        picks the admission/coalescing class; FIFO order holds within a
        class and the starvation bound keeps lower classes moving.
        Under a full bounded queue, ``reject``/unsheddable admission
        raises :class:`OverloadError` synchronously (fail fast — the
        request never queued) and ``shed_oldest`` may fail OTHER queued
        futures with :class:`SheddedError`."""
        if len(xs) != self._n_inputs:
            raise ValueError(f"model has {self._n_inputs} input(s), got "
                             f"{len(xs)}")
        # copy=True: submit() returns immediately while the rows sit in
        # the queue up to max_wait_ms (longer under load) — a caller
        # reusing its buffer must not mutate an in-flight request, so
        # the engine owns its copy from the moment submit() returns
        arrs = []
        for i, (a, d) in enumerate(zip(xs, self._in_dtypes)):
            try:
                arrs.append(np.array(a, dtype=d, copy=True))
            except (ValueError, TypeError) as e:
                # a ragged/uncoercible payload must name the offending
                # input, not surface numpy's opaque internals
                raise ValueError(
                    f"input {i}: cannot coerce to a "
                    f"{np.dtype(d).name} array of rows shaped "
                    f"{self._in_shapes[i]}: {e}") from e
        arrs = tuple(arrs)
        if any(a.ndim == 0 for a in arrs):
            raise ValueError("request inputs must have a leading row "
                             "dimension (shape (n, ...))")
        n = int(arrs[0].shape[0])
        if n < 1:
            raise ValueError("empty request (0 rows)")
        if any(a.shape[0] != n for a in arrs):
            raise ValueError(f"inputs disagree on row count: "
                             f"{[a.shape[0] for a in arrs]}")
        for i, (a, want) in enumerate(zip(arrs, self._in_shapes)):
            # reject the malformed request HERE: packed into a batch,
            # its bad trailing shape would fail the whole dispatch and
            # poison every innocent request coalesced with it
            if tuple(a.shape[1:]) != want:
                raise ValueError(
                    f"input {i}: request rows shaped {tuple(a.shape[1:])} "
                    f"do not match the model input {want}")
        fut: Future = Future()
        t0 = self.clock()
        deadline = None if deadline_ms is None else t0 + deadline_ms / 1e3
        self.metrics.record_submitted()
        metrics = self.metrics
        # span tracing (docs/observability.md): one trace id per
        # sampled logical request; trace_done records its ONE terminal
        # `request` span — phase names the outcome, and the per-phase
        # span counts reconcile exactly with the metrics counters
        tr = self._tracer
        trace = tr.new_trace() if tr.active else None
        trace_done = None
        if trace is not None:
            tname = self.name or "serve"

            def trace_done(phase: str, now: float,
                           _t=trace, _n=n) -> None:
                tr.span("request", _t, t0, now, tid=tname,
                        phase=phase, rows=_n, model=self.name)
        sizes = split_sizes(n, self.max_batch)
        if len(sizes) == 1:
            deadlined = deadline is not None
            done_trace = trace_done

            def on_done(out, now: float) -> bool:
                if isinstance(out, BaseException):
                    if _resolve_future(fut, out):
                        metrics.record_failure(out)
                        if done_trace is not None:
                            done_trace(phase_of(out), now)
                        return True
                    return False
                if _resolve_future(fut, out):
                    metrics.record_request(now - t0, deadlined=deadlined)
                    if done_trace is not None:
                        done_trace("completed", now)
                    return True
                return False

            reqs = [Request(arrs, n, on_done, t0, deadline=deadline,
                            priority=priority, trace=trace)]
        else:
            join = _Join(fut, len(sizes), t0, self.metrics,
                         deadlined=deadline is not None,
                         trace_done=trace_done)
            reqs = []
            off = 0
            for i, sz in enumerate(sizes):
                chunk = tuple(a[off:off + sz] for a in arrs)
                # stale=future.done: once any sibling fails/expires the
                # join, the rest are dead weight and the batcher drops
                # them before packing (atomic expiry/cancel)
                reqs.append(Request(chunk, sz, join.part(i), t0,
                                    deadline=deadline, priority=priority,
                                    stale=fut.done, trace=trace))
                off += sz
        try:
            # atomic: all chunks or none (a concurrent stop() must not
            # strand already-queued chunks of a request whose submit
            # raised)
            blocked_s = self._batcher.submit_all(reqs)
        except OverloadError:
            self.metrics.record_rejected()
            if trace_done is not None:
                trace_done("rejected", self.clock())
            self._health_tick()
            raise
        except RuntimeError as e:
            # the batcher closes exactly when the engine is draining or
            # stopped: surface the typed admission error the errors.py
            # contract promises (`except ServingError` must catch a
            # drain-time refusal, not crash on a bare RuntimeError) —
            # and COUNT it, or record_submitted() above would leave a
            # request with no recorded outcome and break the
            # submitted == requests+rejected+shed+expired+errors
            # reconciliation
            self.metrics.record_rejected()
            if trace_done is not None:
                trace_done("rejected", self.clock())
            raise OverloadError(
                f"engine is not admitting new work ({e})") from e
        if blocked_s > 0:
            self.metrics.record_blocked(blocked_s)
            if trace is not None:
                tr.span("admission_wait", trace, t0, t0 + blocked_s,
                        tid=self.name or "serve")

        def count_cancel(f, _done=trace_done):
            # a client cancel() while queued succeeds without any
            # resolution path ever running (a cancelled future cannot
            # be completed; stale split chunks are even reaped
            # silently) — count the submitted request's outcome HERE,
            # at the cancel instant, or the submitted == outcomes
            # reconciliation (and its terminal-span mirror) leaks one
            # per cancel.  Future.cancel() succeeds at most once, so
            # this fires at most once with cancelled()=True.
            if f.cancelled():
                metrics.record_cancelled()
                if _done is not None:
                    _done("cancelled", self.clock())

        fut.add_done_callback(count_cancel)
        return fut

    def stats(self) -> Dict:
        """Rolling metrics snapshot plus engine shape and health
        (pull-side counterpart of the periodic ``serve_stats``
        events).  ``queue_depth`` is LIVE (the batcher's current
        count, not the last dispatch's view) and
        ``last_dispatch_age_s``/``health`` make a wedged dispatcher
        visible instead of frozen-healthy."""
        return {**self.metrics.snapshot(), "max_batch": self.max_batch,
                "max_wait_ms": self.max_wait_ms,
                "buckets": list(self.buckets),
                "health": self.health,
                "admission": self.admission,
                "max_queue_rows": self.max_queue_rows,
                "peak_queue_rows": self._batcher.peak_rows,
                "quantize": self.quantize}

    # ---- fault injection (FF_FAULT serve_* kinds) ----------------------
    def _fire_serve_faults(self) -> None:
        """Consult the FF_FAULT serve kinds before dispatch
        ``self._n_dispatch`` (flexflow_tpu.faults grammar).  May sleep
        (serve_slow_dispatch — through the injectable ``sleep``), raise
        (serve_fail_dispatch — the normal dispatch-error path fails the
        batch's futures and serving continues) or inject a synthetic
        queue spike (serve_queue_spike — real rows through the real
        admission path, never blocking the dispatcher).  No-op without
        an active plan."""
        if not self._serve_faults:
            return
        idx = self._n_dispatch
        for st in self._serve_faults:
            kind, n = st["kind"], st["n"]
            if kind == "serve_slow_dispatch":
                if st["fired"] < n:
                    st["fired"] += 1
                    self._sleep(st["ms"] / 1e3)
            elif kind == "serve_queue_spike":
                if idx == n and not st["fired"]:
                    st["fired"] += 1
                    # default spike: 4x the packed-batch size — enough
                    # to overflow a typical bounded queue
                    self._inject_spike(st["rows"] or 4 * self.max_batch)
            elif kind == "serve_fail_dispatch":
                st["seen"] += 1
                if st["fired"] < n and st["seen"] % st["every"] == 0:
                    st["fired"] += 1
                    raise RuntimeError(
                        f"FF_FAULT: injected serve dispatch failure "
                        f"{st['fired']}/{n} (dispatch {idx})")

    def _inject_spike(self, rows: int) -> None:
        """Queue-spike fault: push ``rows`` rows of synthetic load
        through the REAL admission path (so shed/reject behavior under
        the spike is the behavior being tested), except that `block`
        downgrades to `reject` — the dispatcher thread must never park
        itself waiting for the room only it can free."""
        from .errors import ServingError
        zeros = tuple(np.zeros((min(rows, self.max_batch),) + s, d)
                      for s, d in zip(self._in_shapes, self._in_dtypes))
        metrics = self.metrics
        policy = "reject" if self.admission == "block" else self.admission

        def on_done(out, now: float) -> bool:
            if isinstance(out, BaseException):
                metrics.record_failure(out)
            return True

        left = rows
        while left > 0:
            sz = min(left, self.max_batch)
            xs = tuple(z[:sz] for z in zeros)
            self.metrics.record_submitted()
            try:
                self._batcher.submit_all(
                    [Request(xs, sz, on_done, self.clock(),
                             priority=-(1 << 30))],
                    admission=policy)
            except ServingError:
                self.metrics.record_rejected()
            except RuntimeError:
                return  # batcher closed mid-spike: drain wins
            left -= sz

    # ---- fleet-managed (external) dispatch -----------------------------
    def begin_external_dispatch(self) -> "ServingEngine":
        """Fleet mode: mark the engine live WITHOUT its own dispatcher
        thread — a :class:`~flexflow_tpu.serving.fleet.FleetEngine`
        drives :meth:`dispatch_pending` from ONE shared dispatcher,
        interleaving this engine's packed batches with its co-resident
        tenants' under weighted-fair scheduling.  The producer side
        (submit, admission, deadlines, priorities) behaves exactly as
        under :meth:`start`."""
        with self._lifecycle:
            if self._stopped:
                raise RuntimeError(
                    "engine was stopped; create a new ServingEngine")
            if self._thread is not None:
                raise RuntimeError(
                    "engine already runs its own dispatcher thread")
            self._serve_faults = _load_serve_faults()
            self._external = True
        self._health_tick()
        return self

    @property
    def has_pending(self) -> bool:
        """Whether the engine has queued work an external dispatcher
        should schedule (fleet mode)."""
        return self._batcher.queue_depth > 0

    def dispatch_pending(self) -> Optional[float]:
        """Externally-driven dispatch step (fleet mode): pop ONE due
        coalesced batch (non-blocking) and dispatch it.  Returns the
        wall seconds the dispatch+fetch took — the device-time the
        fleet's fair scheduler charges this tenant — or None when
        nothing was due.  Error containment matches the owned
        dispatcher thread: a poisoned batch fails only its own futures
        and the time spent is still charged."""
        reqs = self._batcher.poll()
        if not reqs:
            return None
        t0 = self.clock()
        self._dispatch_guarded(reqs)
        return max(0.0, self.clock() - t0)

    # ---- dispatcher thread ---------------------------------------------
    def _dispatch_loop(self) -> None:
        while True:
            reqs = self._batcher.next_batch()
            if reqs is None:
                return  # closed and drained
            self._dispatch_guarded(reqs)

    def _dispatch_guarded(self, reqs) -> None:
        try:
            self._fire_serve_faults()
            self._dispatch_batch(reqs)
        except BaseException as e:  # noqa: BLE001 — one poisoned
            # batch must fail ITS futures, not kill the dispatcher:
            # the engine keeps serving subsequent batches.  on_done
            # reports whether it completed the LOGICAL request (and
            # records the failure class), so split chunks count
            # their request once — the same population serve_stats'
            # ``errors`` counter reports.
            self._consec_errors += 1
            now = self.clock()
            failed = sum(1 for r in reqs if r.on_done(e, now))
            # one structured line per failed dispatch: a failure
            # storm must be visible in the event stream, not only
            # as a counter clients discover via exceptions
            get_logger("serve").event(
                "serve_dispatch_error", model=self.name,
                dispatch=self._n_dispatch,
                error=f"{type(e).__name__}: {e}"[:300],
                failed_requests=failed,
                errors_total=self.metrics.total_errors)
            # post-mortem: the flight ring now holds this dispatch's
            # request spans + the error event — dump it (no-op unless
            # FF_FLIGHT_DIR is set; rate-limited under storms)
            flight_dump("serve_dispatch_error",
                        extra={"model": self.name,
                               "dispatch": self._n_dispatch,
                               "error": f"{type(e).__name__}: {e}"[:300],
                               "failed_requests": failed})
            self._health_tick()

    def _dispatch_batch(self, reqs) -> None:
        import jax

        model = self.model
        rows = sum(r.n for r in reqs)
        bucket = bucket_for(rows, self.buckets)
        depth = self._batcher.queue_depth
        # the ONE tracing check on the dispatch hot path: a single
        # lock-free bool read; everything below keys off the local
        tr = self._tracer
        traced = tr.active
        t0 = self.clock()
        packed = []
        for j in range(self._n_inputs):
            block = (reqs[0].xs[j] if len(reqs) == 1 else
                     np.concatenate([r.xs[j] for r in reqs], axis=0))
            packed.append(block)
        if rows < bucket:
            # the ONE zero-padding rule, shared with predict()'s tail
            packed = list(model._pad_tail(packed, bucket))
        batch = tuple(model._shard_infer_batch(
            tuple(packed) + (model._dummy_label(bucket),)))
        idx = self._n_dispatch
        self._n_dispatch = idx + 1
        # look the executable up through the MODEL's cache (a dict hit
        # when warm), not the startup snapshot: a model re-compile()
        # clears model._fwd_compiled, and dispatching a stale
        # executable lowered from the old graph would silently diverge
        # from predict()
        fwd = model.forward_compiled(bucket)
        t_pack = self.clock() if traced else 0.0
        with jax.profiler.StepTraceAnnotation("serve", step_num=idx):
            out = fwd(model._params, batch)
            t_exec = self.clock() if traced else 0.0
            # the ONE host fetch for the whole packed batch — per-request
            # outputs are sliced from it below (RL005 bans any host sync
            # inside the scatter loop)
            host = np.asarray(jax.device_get(out))
        now = self.clock()
        # the dispatch succeeded the moment the fetch returned: reset
        # the error streak and emit the recovery edge BEFORE scattering
        # — a client whose future just resolved must never observe a
        # stale `degraded`, and a concurrent stop() right after
        # result() must not swallow the degraded->serving transition
        self._consec_errors = 0
        self._health_tick()
        # a bucket re-lowered mid-serve (model re-compile, reshard)
        # re-traces: drain any fresh fallback records (no-op when warm)
        model._surface_runtime_fallbacks()
        self.metrics.record_dispatch(rows, bucket, len(reqs), depth,
                                     now - t0)
        off = 0
        for r in reqs:
            # copy, not a view: a view would keep the whole packed
            # bucket buffer alive for as long as a client retains one
            # request's rows
            r.on_done(host[off:off + r.n].copy(), now)
            off += r.n
        if traced:
            t_scatter = self.clock()
            tname = self.name or "serve"
            # per-request: the time each sampled request sat coalescing
            # in the micro-batcher (submit -> packed into this dispatch)
            for r in reqs:
                if r.trace is not None:
                    tr.span("queue", r.trace, r.t_submit, t0, tid=tname,
                            dispatch=idx)
            # dispatch-scope: the pack/dispatch/fetch/scatter quartet
            # (trace=None — they belong to the packed batch, whose
            # member trace ids ride in args)
            traces = [r.trace for r in reqs if r.trace is not None]
            tr.span("pack", None, t0, t_pack, tid=tname, dispatch=idx,
                    rows=rows, bucket=bucket, requests=len(reqs))
            tr.span("dispatch", None, t_pack, t_exec, tid=tname,
                    dispatch=idx, bucket=bucket, traces=traces)
            tr.span("fetch", None, t_exec, now, tid=tname, dispatch=idx)
            tr.span("scatter", None, now, t_scatter, tid=tname,
                    dispatch=idx)
        if self.stats_every and self._n_dispatch % self.stats_every == 0:
            self.metrics.emit(extra={"max_batch": self.max_batch,
                                     "health": self.health})


def _load_serve_faults() -> List[Dict]:
    """Materialize the FF_FAULT serve_* specs into per-engine firing
    state (start() calls this once per engine; the cached plan() check
    keeps the no-FF_FAULT path a None-test)."""
    out: List[Dict] = []
    for spec in faults.serve_faults():
        out.append({
            "kind": spec.kind,
            "n": int(spec.arg),
            "ms": float(spec.extras.get("ms", "50")),
            "every": max(1, int(spec.extras.get("every", "1"))),
            "rows": int(spec.extras.get("rows", "0")),
            "seen": 0,
            "fired": 0,
        })
    return out
