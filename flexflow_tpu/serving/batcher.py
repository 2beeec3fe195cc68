"""Dynamic micro-batcher — the request-coalescing half of the serving
engine (docs/serving.md).

Pure queueing logic, deliberately free of jax: requests enter a
thread-safe priority-class queue via :meth:`MicroBatcher.submit`; the
dispatcher pulls coalesced batches with :meth:`next_batch`, which
returns as soon as ``max_batch`` rows are pending OR the OLDEST pending
request has waited ``max_wait_ms`` (the latency floor under light load
— a lone request is never parked longer than the deadline waiting for
company).  Bucket selection (`bucket_for`) and oversize splitting
(`split_sizes`) are module-level pure functions so the boundary cases
pin down in unit tests without threads or devices.

Overload is a first-class regime (docs/serving.md "Overload, SLOs &
degradation"):

* the queue is BOUNDED (``max_queue_rows``; 0 = unbounded) and
  ``submit`` applies an admission policy when it is full — ``block``
  (wait for room), ``reject`` (raise :class:`~.errors.OverloadError`,
  nothing enqueued) or ``shed_oldest`` (evict the oldest queued request
  of the lowest priority class ≤ the incoming one, failing it with
  :class:`~.errors.SheddedError`).  ``block`` admission is
  deliberately unordered: woken producers race for freed room, so
  under sustained saturation a LARGE blocked request can be outrun
  indefinitely by smaller ones — callers needing bounded admission
  latency under overload should prefer ``reject``/``shed_oldest``
  (+ deadlines), which is what the overload sweep recommends;
* requests carry an optional absolute ``deadline``: queued work whose
  deadline has passed is expired BEFORE packing (its ``on_done`` fires
  with :class:`~.errors.DeadlineExceeded`) so a dead request never
  burns a device dispatch;
* requests carry an integer ``priority`` class (higher = served
  first); coalescing prefers higher classes while preserving FIFO
  within a class, and a starving class — oldest request waiting ≥
  ``starvation_ms`` — jumps the priority order (aging bound: low
  priority means "later", never "never").

With the defaults (unbounded queue, no deadlines, one priority class)
every path above is skipped and the batcher is the exact FIFO it was
before overload handling existed — the un-overloaded engine stays
bit-identical.

The wall clock is injectable (``clock=``) — the deadline/overload tests
drive a fake clock through `poll()` instead of sleeping.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..obs import lockwatch
from .errors import DeadlineExceeded, OverloadError, SheddedError

ADMISSION_POLICIES = ("block", "reject", "shed_oldest")


def derive_buckets(max_batch: int, spec: str = "") -> Tuple[int, ...]:
    """The engine's shape buckets: ``spec`` ("2,4,16,64") when given,
    else powers of two ``2, 4, ..., max_batch``.  Always sorted,
    deduplicated, and CLOSED under the engine's needs: ``max_batch``
    itself is always a bucket (every coalesced batch has a covering
    bucket), and every bucket is <= ``max_batch``.

    The default set starts at 2, not 1: a single-row program lowers to
    a matrix-VECTOR kernel whose accumulation order differs from the
    matrix-matrix path by ~1 ulp, so a bucket-1 dispatch would break
    packing-invariance (the same request returning different bits
    depending on whether the batcher coalesced it with neighbors —
    tests/test_serving.py pins engine == predict bit-identically).  A
    lone 1-row request pads one row into bucket 2; bucket 1 remains
    available explicitly via ``spec`` for callers that prefer the
    smaller program over bitwise packing-invariance."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if spec:
        try:
            buckets = sorted({int(v) for v in spec.split(",") if v.strip()})
        except ValueError:
            raise ValueError(f"bad bucket spec {spec!r} (want e.g. "
                             f"'2,4,16,64')")
        if not buckets or buckets[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {spec!r}")
        if buckets[-1] > max_batch:
            raise ValueError(f"bucket {buckets[-1]} exceeds max_batch "
                             f"{max_batch}")
    else:
        buckets, b = [], 2
        while b < max_batch:
            buckets.append(b)
            b *= 2
    if not buckets or buckets[-1] != max_batch:
        buckets.append(max_batch)
    return tuple(buckets)


def bucket_for(n: int, buckets: Sequence[int]) -> Optional[int]:
    """Smallest bucket covering ``n`` rows; None when ``n`` exceeds the
    largest bucket (the caller splits first — `split_sizes`)."""
    for b in buckets:
        if b >= n:
            return b
    return None


def split_sizes(n: int, max_batch: int) -> List[int]:
    """Chunk row counts for an oversize request: ``max_batch``-row
    chunks plus the remainder (order preserved — the engine reassembles
    chunk outputs by offset)."""
    if n <= max_batch:
        return [n]
    sizes = [max_batch] * (n // max_batch)
    if n % max_batch:
        sizes.append(n % max_batch)
    return sizes


class Request:
    """One queued unit of work: ``xs`` is a tuple of per-input row
    blocks (all leading dim ``n``); ``on_done(outputs, now)`` fires on
    the dispatcher thread once the packed batch containing this request
    has been fetched (`outputs` is this request's row slice, or an
    exception on the dispatch error / expiry / shed path) and returns
    True iff this call completed the LOGICAL request's future (split
    chunks share one — the error accounting counts completions, not
    chunks).

    ``deadline`` is an ABSOLUTE clock() time after which the request is
    expired instead of packed (None = no deadline); ``priority`` is the
    admission class (higher = served first; default 0); ``stale`` is an
    optional zero-arg predicate — True means the logical request is
    already resolved (a sibling chunk expired/failed, or the client
    cancelled) and this entry is dropped silently at the next scan
    instead of burning dispatch rows; ``trace`` is the request's
    sampled trace id (obs.trace) or None — the batcher never reads it,
    the dispatcher stamps its ``queue`` span with it."""

    __slots__ = ("xs", "n", "on_done", "t_submit", "deadline", "priority",
                 "stale", "trace")

    def __init__(self, xs, n: int, on_done, t_submit: float,
                 deadline: Optional[float] = None, priority: int = 0,
                 stale: Optional[Callable[[], bool]] = None,
                 trace: Optional[str] = None):
        self.xs = xs
        self.n = n
        self.on_done = on_done
        self.t_submit = t_submit
        self.deadline = deadline
        self.priority = int(priority)
        self.stale = stale
        self.trace = trace

    @property
    def _watched(self) -> bool:
        return self.deadline is not None or self.stale is not None


class MicroBatcher:
    """Thread-safe coalescing queue between `submit()` callers and the
    single dispatcher thread, with bounded-queue admission control."""

    def __init__(self, max_batch: int, max_wait_ms: float,
                 clock: Callable[[], float] = time.monotonic,
                 max_queue_rows: int = 0, admission: str = "block",
                 starvation_ms: float = 0.0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission policy {admission!r} "
                f"(want one of {', '.join(ADMISSION_POLICIES)})")
        if 0 < max_queue_rows < max_batch:
            raise ValueError(
                f"max_queue_rows {max_queue_rows} < max_batch {max_batch}: "
                f"a full batch could never queue")
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.max_queue_rows = int(max_queue_rows)
        self.admission = admission
        self.starvation_s = float(starvation_ms) / 1e3
        self.clock = clock
        self._cv = lockwatch.condition("MicroBatcher._cv")
        # priority class -> FIFO deque (ONE class 0 deque in the default
        # path — identical semantics to the plain FIFO this replaced)
        self._classes: Dict[int, deque] = {}  # guarded_by: self._cv
        self._rows = 0        # guarded_by: self._cv
        self._count = 0       # guarded_by: self._cv
        self._watch = 0       # guarded_by: self._cv
        self._peak_rows = 0   # guarded_by: self._cv
        # the absolute time the dispatcher's current cv.wait will
        # self-expire, while it is parked in next_batch (-inf while it
        # is awake or absent): submit only needs to wake it for an
        # incoming DEADLINE that precedes this — notifying on every
        # deadlined submit would re-introduce the per-submit GIL
        # ping-pong the state-change-only notify below exists to avoid
        self._armed_wake = float("-inf")  # guarded_by: self._cv
        self._closed = False  # guarded_by: self._cv

    # ---- producer side -------------------------------------------------
    def submit(self, req: Request) -> float:
        return self.submit_all((req,))

    def submit_all(self, reqs: Sequence[Request],
                   admission: Optional[str] = None) -> float:
        """Enqueue ``reqs`` atomically: either every request is
        accepted or none is (closed batcher, rejected/unsheddable
        overload) — the chunks of one split oversize request must never
        half-enqueue around a concurrent close() or a full queue, which
        would drain orphan chunks whose join future the caller never
        received.

        Applies the admission policy when the queue bound is set
        (``admission=`` overrides the instance policy — the engine's
        fault-injected queue spikes must never self-deadlock blocking
        on the dispatcher thread).  Returns the seconds spent blocked
        for admission (0.0 except under ``block`` on a full queue)."""
        if not reqs:
            return 0.0  # uniform no-op across policies (shed_oldest
            #             would otherwise min() over an empty sequence)
        total = 0
        for req in reqs:
            if req.n > self.max_batch:
                raise ValueError(
                    f"request of {req.n} rows exceeds max_batch "
                    f"{self.max_batch}; split first (split_sizes)")
            total += req.n
        policy = admission or self.admission
        blocked_s = 0.0
        shed: List[Request] = []
        overload: Optional[OverloadError] = None
        with self._cv:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if self.max_queue_rows > 0:
                if total > self.max_queue_rows:
                    raise OverloadError(
                        f"request of {total} rows exceeds the queue bound "
                        f"serve_max_queue_rows={self.max_queue_rows}")
                if policy == "block":
                    t0 = self.clock()
                    while (self._rows + total > self.max_queue_rows
                           and not self._closed):
                        self._cv.wait()
                    blocked_s = self.clock() - t0
                    if self._closed:
                        raise RuntimeError("batcher is closed")
                elif policy == "reject":
                    if self._rows + total > self.max_queue_rows:
                        overload = OverloadError(
                            f"queue full ({self._rows} rows pending, "
                            f"bound {self.max_queue_rows}): request of "
                            f"{total} rows rejected")
                elif policy == "shed_oldest":
                    shed = self._evict_for(
                        total, min(r.priority for r in reqs))
                    if self._rows + total > self.max_queue_rows:
                        overload = OverloadError(
                            f"queue full of higher-priority work "
                            f"({self._rows} rows pending, bound "
                            f"{self.max_queue_rows}): request of {total} "
                            f"rows not admitted")
            if overload is None:
                was_rows = self._rows
                was_empty = self._count == 0
                for req in reqs:
                    self._classes.setdefault(req.priority,
                                             deque()).append(req)
                    self._rows += req.n
                    self._count += 1
                    if req._watched:
                        self._watch += 1
                self._peak_rows = max(self._peak_rows, self._rows)
                # wake the dispatcher only on a state change it must act
                # on: the queue turning nonempty (a deadline now needs
                # arming), the batch turning full (dispatch now), or a
                # request deadline that precedes the wake it is parked
                # on (computed before this deadline existed — without a
                # wake, expiry would fire up to max_wait late instead
                # of AT the deadline).  Notifying every submit would
                # wake it dozens of times per batch just to re-sleep —
                # measured ~3x engine throughput lost to the GIL
                # ping-pong under a hot submit loop.  notify_all, not
                # notify: producers blocked for admission share this
                # condition, and a lone notify could wake one of THEM
                # instead of the dispatcher.
                if (was_empty or was_rows < self.max_batch <= self._rows
                        or any(r.deadline is not None
                               and r.deadline < self._armed_wake
                               for r in reqs)):
                    self._cv.notify_all()
        # fire shed callbacks OUTSIDE the lock: a future callback may
        # re-enter submit(), and the condition's lock is not re-entrant
        if shed:
            now = self.clock()
            for r in shed:
                r.on_done(SheddedError(
                    f"shed after queueing {now - r.t_submit:.3f}s to admit "
                    f"newer work (shed_oldest, bound "
                    f"{self.max_queue_rows} rows)"), now)
        if overload is not None:
            raise overload
        return blocked_s

    def _evict_for(self, need_rows: int,  # guarded_by: self._cv
                   incoming_priority: int) -> List[Request]:
        """shed_oldest eviction (lock held): pop the oldest request of
        the LOWEST priority class not above the incoming request's —
        shedding never displaces strictly higher-priority work — until
        ``need_rows`` fit.  Evicts NOTHING when even shedding every
        eligible victim could not make room (the higher-priority
        remainder still overflows): the incoming request is refused
        either way, and killing queued work for a request that cannot
        be admitted would be pure loss.  Returns the victims; the
        caller fails them outside the lock."""
        eligible = sum(r.n for p, dq in self._classes.items()
                       if p <= incoming_priority for r in dq)
        if self._rows - eligible + need_rows > self.max_queue_rows:
            return []
        out: List[Request] = []
        while self._rows + need_rows > self.max_queue_rows:
            victim_cls = min(
                (p for p, dq in self._classes.items()
                 if dq and p <= incoming_priority), default=None)
            if victim_cls is None:
                break
            r = self._classes[victim_cls].popleft()
            if not self._classes[victim_cls]:
                del self._classes[victim_cls]
            self._unlink(r)
            out.append(r)
        return out

    def close(self) -> None:
        """Stop accepting work; `next_batch` drains what is pending and
        then returns None.  Producers blocked for admission are woken
        and fail with the closed error."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def requeue(self, reqs: Sequence[Request]) -> None:
        """Transfer already-admitted requests INTO this batcher,
        bypassing admission: the hot-swap path moves the outgoing
        engine's pending queue to its replacement at the publish
        boundary (serving/fleet), and work that was admitted once must
        not be re-judged — re-rejecting it would turn a zero-loss swap
        into shed requests.  Order: requeued requests keep their
        original submit times, and within a priority class they land
        ahead of anything the new engine queued meanwhile only if
        requeued first (the fleet publishes before re-opening
        admission, so in practice they do)."""
        with self._cv:
            if self._closed:
                raise RuntimeError("batcher is closed")
            was_empty = self._count == 0
            for req in reqs:
                self._classes.setdefault(req.priority,
                                         deque()).append(req)
                self._rows += req.n
                self._count += 1
                if req._watched:
                    self._watch += 1
            self._peak_rows = max(self._peak_rows, self._rows)
            if reqs and (was_empty or self._rows >= self.max_batch):
                self._cv.notify_all()

    def fail_pending(self) -> List[Request]:
        """Atomically remove EVERYTHING still queued and hand it to the
        caller (drain-timeout stragglers: the engine fails their
        futures).  The queue is empty afterwards; callbacks are the
        caller's job — outside any lock."""
        with self._cv:
            out: List[Request] = []
            for dq in self._classes.values():
                out.extend(dq)
            self._classes.clear()
            self._rows = 0
            self._count = 0
            self._watch = 0
            self._cv.notify_all()
        out.sort(key=lambda r: r.t_submit)
        return out

    # ---- consumer side -------------------------------------------------
    @property
    def queue_depth(self) -> int:
        """Pending requests (live snapshot, for metrics)."""
        with self._cv:
            return self._count

    @property
    def pending_rows(self) -> int:
        with self._cv:
            return self._rows

    @property
    def peak_rows(self) -> int:
        """High-water mark of queued rows over the batcher's lifetime —
        the bounded-queue evidence ``stats()["peak_queue_rows"]`` reports
        (must stay <= max_queue_rows when the bound is set)."""
        with self._cv:
            return self._peak_rows

    def _unlink(self, r: Request) -> None:  # guarded_by: self._cv
        """Accounting for a request leaving the queue (lock held)."""
        self._rows -= r.n
        self._count -= 1
        if r._watched:
            self._watch -= 1

    def _oldest_t(self) -> Optional[float]:  # guarded_by: self._cv
        """Submit time of the oldest queued request (lock held) — class
        heads are each class's oldest, so the min over heads is global."""
        return min((dq[0].t_submit for dq in self._classes.values() if dq),
                   default=None)

    def _ready(self, now: float) -> bool:  # guarded_by: self._cv
        if not self._count:
            return False
        if self._rows >= self.max_batch:
            return True
        oldest = self._oldest_t()
        return oldest is not None and now - oldest >= self.max_wait_s

    def _collect_expired(self, now: float  # guarded_by: self._cv
                         ) -> List[Request]:
        """Remove deadline-expired and stale requests (lock held) and
        return the EXPIRED ones — the caller fires their ``on_done``
        with DeadlineExceeded outside the lock.  Stale entries (logical
        request already resolved — sibling chunk expired/failed, or
        client cancel) are dropped silently: their future is done, and
        dropping them here is what makes split-request expiry atomic
        (no surviving chunk burns a dispatch).  Skipped entirely when
        nothing queued carries a deadline or stale predicate — the
        default path never pays the scan."""
        if not self._watch:
            return []
        fire: List[Request] = []
        freed = False
        for p in list(self._classes):
            dq = self._classes[p]
            dead = []
            for r in dq:
                stale = r.stale is not None and r.stale()
                expired = r.deadline is not None and now >= r.deadline
                if stale or expired:
                    dead.append((r, expired and not stale))
            if not dead:
                # the common wake: nothing to remove — never rebuild a
                # deque just to look (a deep queue with one live
                # deadline would otherwise be copied on every wake)
                continue
            gone = {id(r) for r, _ in dead}
            keep: deque = deque(r for r in dq if id(r) not in gone)
            for r, do_fire in dead:
                self._unlink(r)
                if do_fire:
                    fire.append(r)
            freed = True
            if keep:
                self._classes[p] = keep
            else:
                del self._classes[p]
        if freed:
            self._cv.notify_all()  # room for blocked producers
        return fire

    def _fire_expired(self, fire: List[Request]) -> None:
        if not fire:
            return
        now = self.clock()
        for r in fire:
            r.on_done(DeadlineExceeded(
                f"deadline passed {now - r.deadline:.3f}s ago while "
                f"queued (waited {now - r.t_submit:.3f}s; expired before "
                f"packing, no dispatch burned)"), now)

    def _class_order(self, now: float) -> List[int]:  # guarded_by: self._cv
        """Service order over priority classes (lock held): higher
        class first, EXCEPT that starving classes — oldest request
        waiting >= starvation_ms — jump ahead, oldest-first.  The aging
        bound keeps low-priority latency bounded under sustained
        high-priority load: "low priority" means later, never never."""
        classes = [p for p, dq in self._classes.items() if dq]
        if len(classes) <= 1:
            return classes
        starving = []
        if self.starvation_s > 0:
            starving = [p for p in classes
                        if now - self._classes[p][0].t_submit
                        >= self.starvation_s]
            starving.sort(key=lambda p: self._classes[p][0].t_submit)
        rest = sorted((p for p in classes if p not in starving),
                      reverse=True)
        return starving + rest

    def _take(self, now: float) -> List[Request]:  # guarded_by: self._cv
        """Pop a coalesced batch of at most ``max_batch`` rows (lock
        held): classes in `_class_order`, a FIFO prefix within each
        class (whole requests only — order-preserving, and the scatter
        stays one contiguous slice per request); oversize requests were
        already split at submit.  With one class this is exactly the
        old FIFO-prefix pop."""
        out: List[Request] = []
        rows = 0
        for p in self._class_order(now):
            dq = self._classes[p]
            while dq and rows + dq[0].n <= self.max_batch:
                r = dq.popleft()
                self._unlink(r)
                rows += r.n
                out.append(r)
            if not dq:
                del self._classes[p]
            if rows >= self.max_batch:
                break
        if out:
            self._cv.notify_all()  # room for blocked producers
        return out

    def reap_expired(self) -> int:
        """Expire deadline-passed / stale queued requests NOW without
        popping a batch.  Consumers whose take cadence is not their
        expiry cadence call this at their own boundaries — the
        generation engine's decode loop can run with every slot busy
        for seconds while queued prompts' deadlines lapse, and poll()
        (which would also TAKE work) only runs when a slot frees.
        Returns the number of requests expired.

        The no-deadline case is O(1): ``_watch`` is the live count of
        deadline/stale-bearing requests, and when it is zero this
        returns without reading the clock or entering the scan at all
        — this runs at EVERY decode-step boundary, and the common
        workload queues nothing reapable (pinned in
        tests/test_serving.py: the scan path is never entered)."""
        with self._cv:
            if not self._watch:
                return 0
            fire = self._collect_expired(self.clock())
        self._fire_expired(fire)
        return len(fire)

    def poll(self) -> Optional[List[Request]]:
        """Non-blocking `next_batch`: a coalesced batch if one is due
        (full, past the deadline, or draining after close), else None.
        Expires dead requests first — the fake-clock overload tests
        drive the whole deadline/admission matrix through this."""
        while True:
            with self._cv:
                now = self.clock()
                fire = self._collect_expired(now)
                batch = None
                if not fire and self._count and (self._closed
                                                 or self._ready(now)):
                    batch = self._take(now)
            if not fire:
                return batch
            self._fire_expired(fire)

    def _wake_in(self, now: float) -> Optional[float]:  # guarded_by: self._cv
        """Seconds until the next self-scheduled event (lock held):
        the oldest request's flush deadline, and — when deadlines are
        queued — the earliest expiry (an expired future must fail at
        its deadline, not whenever the next flush happens to look)."""
        wait = None
        oldest = self._oldest_t()
        if oldest is not None:
            wait = oldest + self.max_wait_s - now
        if self._watch:
            ed = min((r.deadline for dq in self._classes.values()
                      for r in dq if r.deadline is not None), default=None)
            if ed is not None:
                wait = ed - now if wait is None else min(wait, ed - now)
        return wait

    def next_batch(self, timeout: Optional[float] = None
                   ) -> Optional[List[Request]]:
        """Block until a batch is due, the batcher is closed AND
        drained (returns None — dispatcher exits), or ``timeout``
        expires (returns None; caller re-checks its stop flag)."""
        deadline = None if timeout is None else self.clock() + timeout
        while True:
            with self._cv:
                now = self.clock()
                fire = self._collect_expired(now)
                if not fire:
                    if self._count and (self._closed or self._ready(now)):
                        return self._take(now)
                    if self._closed and not self._count:
                        return None
                    # sleep until the oldest request's flush deadline /
                    # earliest expiry (or the caller's timeout / a
                    # submit notification)
                    wait = self._wake_in(now)
                    if deadline is not None:
                        if now >= deadline:
                            return None
                        wait = (deadline - now if wait is None
                                else min(wait, deadline - now))
                    # publish when this wait self-expires so submit()
                    # can tell whether an incoming deadline needs a
                    # wake; -inf while awake (it recomputes anyway)
                    self._armed_wake = (float("inf") if wait is None
                                        else now + max(0.0, wait))
                    self._cv.wait(None if wait is None
                                  else max(0.0, wait))
                    self._armed_wake = float("-inf")
                    continue
            self._fire_expired(fire)
