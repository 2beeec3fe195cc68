"""Serving engine (ISSUE 5): shape-bucketed AOT executables + dynamic
micro-batching.

The parity suite pins BIT-IDENTICAL outputs between the engine and
``predict()`` for mixed request sizes across buckets — packing a
request with different neighbors (or padding it into a different
bucket) must never change its bits — on single-device and the n=8 CPU
mesh.  Plus: bucket-selection boundaries and oversize splits,
deadline-flush behavior on a fake clock, a multi-thread submission
smoke test, serving metrics/percentiles and compile-cache idempotence.
"""

import json
import threading

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu import faults
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.serving import (DeadlineExceeded, MicroBatcher,
                                  OverloadError, Request, ServingEngine,
                                  ServingMetrics, SheddedError, bucket_for,
                                  derive_buckets, split_sizes)

BS = 16
NFEAT = 12
NCLS = 5


def _model(mesh_shape=None, max_batch=BS):
    cfg = ff.FFConfig(batch_size=BS, compute_dtype="float32")
    cfg.serve_max_batch = max_batch
    m = ff.FFModel(cfg, mesh=MachineMesh(mesh_shape or {"n": 1}))
    x = m.create_tensor((BS, NFEAT), name="x")
    t = m.dense(x, 24, activation="relu")
    t = m.dense(t, NCLS)
    m.compile(ff.SGDOptimizer(lr=0.1), metrics=["accuracy"])
    m.init_layers(seed=0)
    return m


def _requests(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((s, NFEAT)).astype(np.float32)
            for s in sizes]


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ----------------------------------------------------------------------
# bucket selection / oversize splits (pure functions)
# ----------------------------------------------------------------------
def test_derive_buckets_powers_of_two():
    assert derive_buckets(64) == (2, 4, 8, 16, 32, 64)
    # non-power-of-two max is always its own (largest) bucket
    assert derive_buckets(48) == (2, 4, 8, 16, 32, 48)
    assert derive_buckets(2) == (2,)
    assert derive_buckets(1) == (1,)
    assert derive_buckets(64, "2,16,64") == (2, 16, 64)
    # max_batch joins an explicit list that omits it
    assert derive_buckets(64, "4,16") == (4, 16, 64)
    with pytest.raises(ValueError, match="exceeds max_batch"):
        derive_buckets(16, "4,32")
    with pytest.raises(ValueError, match="bad bucket spec"):
        derive_buckets(16, "a,b")
    with pytest.raises(ValueError, match="max_batch"):
        derive_buckets(0)


def test_bucket_for_exact_boundaries():
    buckets = derive_buckets(64)
    assert bucket_for(1, buckets) == 2
    assert bucket_for(2, buckets) == 2   # exact boundary -> own bucket
    assert bucket_for(3, buckets) == 4
    assert bucket_for(4, buckets) == 4
    assert bucket_for(5, buckets) == 8
    assert bucket_for(33, buckets) == 64
    assert bucket_for(64, buckets) == 64
    assert bucket_for(65, buckets) is None  # oversize: caller splits


def test_split_sizes_oversize_requests():
    assert split_sizes(5, 32) == [5]
    assert split_sizes(32, 32) == [32]
    assert split_sizes(70, 32) == [32, 32, 6]
    assert split_sizes(64, 32) == [32, 32]
    assert sum(split_sizes(1000, 48)) == 1000


# ----------------------------------------------------------------------
# deadline flush (fake clock, no threads)
# ----------------------------------------------------------------------
def _req(n, clock, done):
    return Request((np.zeros((n, 1), np.float32),), n,
                   lambda out, now: done.append((n, out)), clock())


def test_deadline_flush_fake_clock():
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=5.0, clock=clk)
    done = []
    b.submit(_req(3, clk, done))
    assert b.poll() is None          # not full, deadline not reached
    clk.t = 0.0049
    assert b.poll() is None          # 4.9ms < 5ms: still coalescing
    clk.t = 0.0051
    batch = b.poll()                 # deadline passed: flush partial
    assert batch is not None and [r.n for r in batch] == [3]
    assert b.poll() is None          # queue drained


def test_reap_expired_no_deadline_skips_scan(monkeypatch):
    """ISSUE 15 satellite pin: reap_expired() runs at EVERY generation
    decode-step boundary, and with nothing deadline/stale-bearing
    queued (the live ``_watch`` count is zero) it must return without
    entering the queue scan or even reading the clock — the O(1) fast
    path.  A deadline-bearing submit flips the count and the scan
    engages again."""
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=5.0, clock=clk)
    done = []
    for _ in range(3):
        b.submit(_req(1, clk, done))
    entered = []
    orig_scan = b._collect_expired

    def spy(now):
        entered.append(now)
        return orig_scan(now)

    monkeypatch.setattr(b, "_collect_expired", spy)
    reads = []
    real = clk

    def counting_clock():
        reads.append(1)
        return real()

    monkeypatch.setattr(b, "clock", counting_clock)
    n_reads = len(reads)
    assert b.reap_expired() == 0
    assert entered == [], "scan path entered with no watched request"
    assert len(reads) == n_reads, "clock read on the O(1) path"
    # a deadline-bearing request flips _watch: the scan engages, and
    # the expiry fires through the (spied) scan path
    b.submit(Request((np.zeros((1, 1), np.float32),), 1,
                     lambda out, now: done.append(("dl", out)),
                     clk.t, deadline=clk.t + 5.0))
    assert b.reap_expired() == 0 and len(entered) == 1  # scan, no expiry
    clk.t += 10.0
    assert b.reap_expired() == 1 and len(entered) == 2
    assert isinstance(done[-1][1], DeadlineExceeded)
    # the expired request left the queue; _watch is back to zero and
    # the fast path re-engages
    n_scans = len(entered)
    assert b.reap_expired() == 0 and len(entered) == n_scans


def test_full_batch_flushes_without_deadline():
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=1e9, clock=clk)
    done = []
    b.submit(_req(5, clk, done))
    assert b.poll() is None
    b.submit(_req(3, clk, done))     # 5+3 == max_batch: due NOW
    batch = b.poll()
    assert [r.n for r in batch] == [5, 3]


def test_batcher_fifo_prefix_and_close_drain():
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=1e9, clock=clk)
    done = []
    for n in (4, 3, 6):
        b.submit(_req(n, clk, done))
    assert b.pending_rows == 13 and b.queue_depth == 3
    b.close()                        # drain mode: everything is due
    assert [r.n for r in b.poll()] == [4, 3]  # 4+3 fits, +6 would not
    assert [r.n for r in b.poll()] == [6]
    assert b.next_batch() is None    # closed AND drained
    with pytest.raises(RuntimeError, match="closed"):
        b.submit(_req(1, clk, done))


def test_batcher_rejects_oversize_request():
    b = MicroBatcher(max_batch=4, max_wait_ms=1.0)
    with pytest.raises(ValueError, match="split first"):
        b.submit(Request((np.zeros((5, 1)),), 5, lambda o, t: None, 0.0))


def test_submit_all_atomic_after_close():
    """Split-request chunks enqueue all-or-nothing: after close() the
    whole group is rejected and NOTHING is queued (a half-enqueued
    oversize request would drain orphan chunks nobody waits on)."""
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=1.0, clock=clk)
    b.close()
    chunks = [Request((np.zeros((2, 1)),), 2, lambda o, t: None, 0.0)
              for _ in range(3)]
    with pytest.raises(RuntimeError, match="closed"):
        b.submit_all(chunks)
    assert b.queue_depth == 0 and b.pending_rows == 0


# ----------------------------------------------------------------------
# deadlines: queued work expires BEFORE packing (fake clock, no threads)
# ----------------------------------------------------------------------
def _dreq(n, clock, done, deadline=None, priority=0):
    return Request((np.zeros((n, 1), np.float32),), n,
                   lambda out, now: done.append((n, out)) or True, clock(),
                   deadline=deadline, priority=priority)


def test_deadline_expires_queued_request_before_packing():
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=5.0, clock=clk)
    done = []
    b.submit(_dreq(3, clk, done, deadline=0.003))
    assert b.poll() is None and not done   # alive: not due, not expired
    clk.t = 0.004                          # past the deadline, pre-flush
    assert b.poll() is None                # expired, NOT dispatched
    assert len(done) == 1
    n, out = done[0]
    assert n == 3 and isinstance(out, DeadlineExceeded)
    assert b.queue_depth == 0 and b.pending_rows == 0
    clk.t = 1.0
    assert b.poll() is None                # nothing left to flush


def test_deadline_mixed_expiry_packs_only_survivors():
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=5.0, clock=clk)
    done = []
    b.submit(_dreq(3, clk, done, deadline=0.002))
    b.submit(_dreq(4, clk, done))          # no deadline
    clk.t = 0.006                          # flush due AND first expired
    batch = b.poll()
    assert [r.n for r in batch] == [4]
    assert len(done) == 1 and isinstance(done[0][1], DeadlineExceeded)


def test_submit_all_empty_is_a_noop_under_every_policy():
    clk = FakeClock()
    for policy in ("block", "reject", "shed_oldest"):
        b = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk,
                         max_queue_rows=8, admission=policy)
        done = []
        b.submit(_dreq(4, clk, done))
        b.submit(_dreq(4, clk, done))       # full: the shed/reject
        assert b.submit_all([]) == 0.0      # branches would otherwise run
        assert b.pending_rows == 8 and not done


def test_deadlined_submit_wakes_a_parked_dispatcher():
    """A request whose deadline precedes the dispatcher's scheduled
    wake must NOTIFY it: the parked wait was computed before this
    deadline existed, and without a wake the expiry would fire up to
    max_wait late instead of AT the deadline (real clock; the consumer
    is event-driven — the only waiting is on the expiry itself)."""
    import time as _time
    b = MicroBatcher(max_batch=8, max_wait_ms=60_000.0)
    expired = threading.Event()

    def on_done(out, now):
        if isinstance(out, DeadlineExceeded):
            expired.set()
        return True

    consumer = threading.Thread(target=b.next_batch, daemon=True)
    consumer.start()
    # park the dispatcher on the 60s flush deadline of a no-deadline
    # request, then submit one that expires almost immediately
    b.submit(Request((np.zeros((2, 1), np.float32),), 2,
                     lambda o, t: True, b.clock()))
    b.submit(Request((np.zeros((1, 1), np.float32),), 1, on_done,
                     b.clock(), deadline=b.clock() + 0.01))
    assert expired.wait(timeout=5), \
        "deadline expiry waited for the 60s flush instead of the wake"
    b.close()
    consumer.join(timeout=5)
    assert not consumer.is_alive()


def test_next_batch_wakes_for_earliest_deadline():
    """The dispatcher's self-scheduled wake must include queued
    deadlines: a request whose deadline precedes the flush deadline
    fails AT its deadline, not whenever the flush happens to look."""
    clk = FakeClock()
    b = MicroBatcher(max_batch=8, max_wait_ms=5000.0, clock=clk)
    done = []
    b.submit(_dreq(2, clk, done, deadline=0.010))
    with b._cv:
        wake = b._wake_in(clk())
    assert wake == pytest.approx(0.010)    # deadline, not the 5s flush


# ----------------------------------------------------------------------
# admission control: bounded queue, block / reject / shed_oldest
# ----------------------------------------------------------------------
def test_admission_reject_fails_fast_and_enqueues_nothing():
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk,
                     max_queue_rows=8, admission="reject")
    done = []
    b.submit(_dreq(4, clk, done))
    b.submit(_dreq(4, clk, done))          # bound reached
    with pytest.raises(OverloadError, match="queue full"):
        b.submit(_dreq(2, clk, done))
    assert b.pending_rows == 8 and b.queue_depth == 2
    # a single logical request bigger than the whole bound can never be
    # admitted under any policy: reject it up front
    with pytest.raises(OverloadError, match="exceeds the queue bound"):
        b.submit_all([_dreq(4, clk, done), _dreq(4, clk, done),
                      _dreq(4, clk, done)])
    assert b.pending_rows == 8             # nothing half-enqueued


def test_admission_shed_oldest_evicts_and_bounds_queue():
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk,
                     max_queue_rows=8, admission="shed_oldest")
    done = []
    b.submit(_dreq(4, clk, done))
    clk.t = 0.001
    b.submit(_dreq(4, clk, done))
    clk.t = 0.002
    b.submit(_dreq(4, clk, done))          # sheds the OLDEST (t=0)
    assert len(done) == 1
    n, out = done[0]
    assert n == 4 and isinstance(out, SheddedError)
    assert b.pending_rows == 8 and b.peak_rows <= 8
    # FIFO order of the survivors is preserved
    b.close()
    assert [r.t_submit for r in b.poll()] == [0.001]
    assert [r.t_submit for r in b.poll()] == [0.002]


def test_shed_never_displaces_higher_priority_work():
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk,
                     max_queue_rows=8, admission="shed_oldest")
    done = []
    b.submit(_dreq(4, clk, done, priority=5))
    b.submit(_dreq(4, clk, done, priority=5))
    # a low-priority request cannot shed the queued high-priority work:
    # it is the one refused
    with pytest.raises(OverloadError, match="higher-priority"):
        b.submit(_dreq(4, clk, done, priority=0))
    assert not done and b.pending_rows == 8
    # ...and a doomed request must not shed eligible victims either,
    # when the higher-priority remainder would still overflow: here 2
    # low-priority rows ARE sheddable, but evicting them cannot fit the
    # incoming 4 rows next to 6 high-priority ones — nothing is evicted
    b2 = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk,
                      max_queue_rows=8, admission="shed_oldest")
    done2 = []
    b2.submit(_dreq(2, clk, done2, priority=0))
    b2.submit(_dreq(4, clk, done2, priority=5))
    b2.submit(_dreq(2, clk, done2, priority=5))
    with pytest.raises(OverloadError):
        b2.submit(_dreq(4, clk, done2, priority=0))
    assert not done2 and b2.pending_rows == 8   # pure-loss shed avoided
    # an equal-priority request CAN shed the oldest equal-priority one
    b.submit(_dreq(4, clk, done, priority=5))
    assert len(done) == 1 and isinstance(done[0][1], SheddedError)


def test_admission_block_waits_for_room():
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=0.0, clock=clk,
                     max_queue_rows=8, admission="block")
    done = []
    b.submit(_dreq(4, clk, done))
    b.submit(_dreq(4, clk, done))          # full
    out = {}

    def producer():
        out["blocked_s"] = b.submit(_dreq(2, clk, done))

    th = threading.Thread(target=producer)
    th.start()
    # free room from the consumer side (max_wait 0: always due); the
    # blocked producer is woken by the take — no sleeps involved
    taken = []
    while th.is_alive():
        got = b.poll()
        if got:
            taken.extend(r.n for r in got)
    th.join(timeout=30)
    assert not th.is_alive()
    assert out["blocked_s"] >= 0.0
    # drain the rest: the late request made it into the queue
    b.close()
    while True:
        got = b.poll()
        if not got:
            break
        taken.extend(r.n for r in got)
    assert taken[:2] == [4, 4] and 2 in taken


def test_fail_pending_clears_everything_for_drain():
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk)
    done = []
    b.submit(_dreq(3, clk, done))
    clk.t = 0.001
    b.submit(_dreq(4, clk, done, priority=2))
    stragglers = b.fail_pending()
    assert [r.t_submit for r in stragglers] == [0.0, 0.001]  # oldest first
    assert b.queue_depth == 0 and b.pending_rows == 0
    assert b.poll() is None


# ----------------------------------------------------------------------
# priority classes: strict order, FIFO within class, aging bound
# ----------------------------------------------------------------------
def test_priority_order_fifo_within_class():
    clk = FakeClock()
    b = MicroBatcher(max_batch=4, max_wait_ms=1e9, clock=clk,
                     starvation_ms=0.0)
    done = []
    for i, (n, pri) in enumerate([(2, 0), (2, 5), (2, 0), (2, 5)]):
        clk.t = i * 0.001
        b.submit(_dreq(n, clk, done, priority=pri))
    b.close()
    first = b.poll()
    second = b.poll()
    # class 5 served first, FIFO within it; then class 0, FIFO
    assert [r.t_submit for r in first] == [0.001, 0.003]
    assert [r.t_submit for r in second] == [0.0, 0.002]


def test_anti_starvation_aging_bound_promotes_old_low_priority():
    clk = FakeClock()
    b = MicroBatcher(max_batch=2, max_wait_ms=1.0, clock=clk,
                     starvation_ms=100.0)
    done = []
    b.submit(_dreq(2, clk, done, priority=0))      # t=0, low
    clk.t = 0.150                                  # low now starving
    b.submit(_dreq(2, clk, done, priority=5))      # fresh high
    batch = b.poll()
    assert [r.priority for r in batch] == [0]      # aged class jumps
    batch = b.poll()
    assert [r.priority for r in batch] == [5]
    # without aging, strict priority wins
    b2 = MicroBatcher(max_batch=2, max_wait_ms=1.0, clock=clk,
                      starvation_ms=0.0)
    clk.t = 0.0
    b2.submit(_dreq(2, clk, done, priority=0))
    clk.t = 0.150
    b2.submit(_dreq(2, clk, done, priority=5))
    assert [r.priority for r in b2.poll()] == [5]


# ----------------------------------------------------------------------
# engine <-> predict parity: bit-identical, mixed sizes, both meshes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mesh_shape", [{"n": 1}, {"n": 8}],
                         ids=["single", "distributed"])
def test_engine_predict_parity_bitwise(mesh_shape):
    m = _model(mesh_shape)
    # mixed sizes hit every bucket, exact boundaries (2/4/8/16), the
    # deadline-flush partial path, and the oversize split (40 > 16)
    sizes = [1, 3, 4, 7, 16, 5, 2, 40, 8, 1, 6, 16]
    reqs = _requests(sizes)
    eng = ServingEngine(m, stats_every=0)
    # AOT-warm at startup, in the cache predict() shares (keys are
    # (bucket, exec_digest) — the digest half keeps fleet tenants'
    # executables apart, tests/test_fleet.py)
    assert set(eng.buckets) <= {b for b, _ in m._fwd_compiled}
    with eng:
        futs = [eng.submit(r) for r in reqs]
        outs = [f.result(timeout=60) for f in futs]
    want = m.predict(np.concatenate(reqs), batch_size=BS)
    # results own their memory — a view would pin the whole packed
    # bucket buffer for as long as a client keeps one request's rows
    assert all(o.base is None for o in outs)
    off = 0
    for s, o in zip(sizes, outs):
        assert o.shape == (s, NCLS)
        np.testing.assert_array_equal(o, want[off:off + s],
                                      err_msg=f"request of {s} rows")
        off += s
    snap = eng.stats()
    assert snap["requests"] == len(sizes)
    assert snap["rows"] == sum(sizes)
    assert snap["dispatches"] >= 1


def test_engine_multi_input_model_parity():
    cfg = ff.FFConfig(batch_size=BS, compute_dtype="float32")
    cfg.serve_max_batch = BS
    m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
    a = m.create_tensor((BS, 6), name="a")
    b = m.create_tensor((BS, 6), name="b")
    t = m.concat([a, b], axis=1)
    t = m.dense(t, 16, activation="relu")
    m.dense(t, NCLS)
    m.compile(ff.SGDOptimizer(lr=0.1), metrics=["accuracy"])
    m.init_layers(seed=0)
    rng = np.random.default_rng(1)
    sizes = [2, 5, 9, 16, 3]
    xa = [rng.standard_normal((s, 6)).astype(np.float32) for s in sizes]
    xb = [rng.standard_normal((s, 6)).astype(np.float32) for s in sizes]
    with ServingEngine(m, stats_every=0) as eng:
        outs = [f.result(timeout=60)
                for f in [eng.submit(p, q) for p, q in zip(xa, xb)]]
    want = m.predict([np.concatenate(xa), np.concatenate(xb)],
                     batch_size=BS)
    off = 0
    for s, o in zip(sizes, outs):
        np.testing.assert_array_equal(o, want[off:off + s])
        off += s


def test_cancelled_future_does_not_kill_dispatcher():
    """A client cancel() on a queued future (the standard move after a
    result(timeout=...) TimeoutError) must be dropped by the scatter —
    not raise InvalidStateError on the dispatcher thread, which would
    hang every subsequent request."""
    m = _model()
    reqs = _requests([3, 4, 5], seed=11)
    eng = ServingEngine(m, stats_every=0)
    # cancel while queued: submit before the dispatcher thread starts
    doomed = eng.submit(reqs[0])
    assert doomed.cancel()
    keep = [eng.submit(r) for r in reqs[1:]]
    eng.start()
    outs = [f.result(timeout=30) for f in keep]
    # the engine must still serve AFTER the cancelled dispatch too
    after = eng.submit(reqs[0]).result(timeout=30)
    eng.stop()
    want = m.predict(np.concatenate(reqs[1:]), batch_size=BS)
    off = 0
    for r, o in zip(reqs[1:], outs):
        np.testing.assert_array_equal(o, want[off:off + len(r)])
        off += len(r)
    np.testing.assert_array_equal(
        after, m.predict(reqs[0], batch_size=BS)[:len(reqs[0])])
    assert doomed.cancelled()


def test_poisoned_batch_fails_only_its_futures_and_serving_continues(
        capsys):
    """A device dispatch that blows up fails THE AFFECTED futures with
    the error and the engine keeps serving subsequent batches — one
    poisoned batch must never wedge the queue.  The failure is counted
    in serve_stats (``errors``) and emitted as a structured
    ``serve_dispatch_error`` event."""
    m = _model()
    eng = ServingEngine(m, stats_every=0)
    boom = {"armed": True}
    orig = m.forward_compiled

    def flaky(bucket):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected dispatch failure")
        return orig(bucket)

    m.forward_compiled = flaky
    try:
        # queued before start: both requests coalesce into the ONE
        # poisoned dispatch
        doomed = [eng.submit(r) for r in _requests([3, 4], seed=5)]
        eng.start()
        errs = [pytest.raises(RuntimeError, f.result, timeout=30)
                for f in doomed]
        assert all("injected dispatch failure" in str(e.value)
                   for e in errs)
        # the dispatcher survived: the next batch serves correctly
        after_req = _requests([5], seed=6)[0]
        after = eng.submit(after_req).result(timeout=30)
    finally:
        m.forward_compiled = orig
        eng.stop()
    np.testing.assert_array_equal(
        after, m.predict(after_req, batch_size=BS)[:5])
    snap = eng.stats()
    assert snap["errors"] == 2          # logical requests, not chunks
    assert snap["requests"] == 1        # only the successful one
    events = [json.loads(l) for l in capsys.readouterr().out.splitlines()
              if l.startswith("{")]
    derr = [e for e in events if e["event"] == "serve_dispatch_error"]
    assert len(derr) == 1
    assert derr[0]["failed_requests"] == 2
    assert "injected dispatch failure" in derr[0]["error"]
    assert derr[0]["errors_total"] == 2


def test_engine_serves_across_reshard():
    """Serving survives a live mesh change: reshard() drops the AOT
    bucket executables, and the dispatcher — which looks executables up
    through the model's cache — re-lowers for the new mesh on the next
    packed batch, still bit-identical to predict()."""
    m = _model({"n": 4})
    req_a, req_b = _requests([6, 9], seed=7)
    with ServingEngine(m, stats_every=0) as eng:
        before = eng.submit(req_a).result(timeout=60)
        np.testing.assert_array_equal(
            before, m.predict(req_a, batch_size=BS)[:6])
        m.reshard(new_mesh={"n": 2})
        assert m._fwd_compiled == {}    # stale executables dropped
        after = eng.submit(req_b).result(timeout=60)
    np.testing.assert_array_equal(
        after, m.predict(req_b, batch_size=BS)[:9])
    assert eng.stats()["errors"] == 0


def test_submit_copies_caller_buffer():
    """submit() returns while the rows are still queued — the engine
    must own a copy so a client reusing its buffer cannot mutate an
    in-flight request."""
    m = _model()
    eng = ServingEngine(m, stats_every=0)
    buf = np.ones((3, NFEAT), np.float32)
    want = m.predict(buf.copy(), batch_size=BS)[:3]
    fut = eng.submit(buf)      # queued; dispatcher not started yet
    buf[:] = -7.0              # client reuses its buffer immediately
    eng.start()
    np.testing.assert_array_equal(fut.result(timeout=30), want)
    eng.stop()


def test_submit_validation():
    m = _model()
    with ServingEngine(m, stats_every=0) as eng:
        with pytest.raises(ValueError, match="input"):
            eng.submit(np.zeros((2, NFEAT), np.float32),
                       np.zeros((2, NFEAT), np.float32))
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros((0, NFEAT), np.float32))
        # a malformed trailing shape is rejected at submit() — packed
        # into a batch it would poison every coalesced neighbor
        with pytest.raises(ValueError, match="do not match"):
            eng.submit(np.zeros((2, NFEAT + 1), np.float32))
        # ...and valid traffic around the rejection still serves
        ok = eng.submit(np.ones((3, NFEAT), np.float32)).result(timeout=30)
        assert ok.shape == (3, NCLS)
    assert eng.stats()["errors"] == 0


# ----------------------------------------------------------------------
# engine-level overload handling (fake clock where the clock matters)
# ----------------------------------------------------------------------
def test_engine_deadline_expires_without_burning_a_dispatch():
    clk = FakeClock()
    m = _model()
    eng = ServingEngine(m, stats_every=0, max_wait_ms=0.0, clock=clk)
    fut = eng.submit(_requests([3], seed=1)[0], deadline_ms=5.0)
    clk.t = 0.010                          # deadline long gone
    eng.start()
    with pytest.raises(DeadlineExceeded, match="no dispatch burned"):
        fut.result(timeout=30)
    snap = eng.stats()
    assert snap["expired"] == 1 and snap["dispatches"] == 0
    # the engine keeps serving: an un-deadlined request goes through
    req = _requests([4], seed=2)[0]
    out = eng.submit(req).result(timeout=30)
    eng.stop()
    np.testing.assert_array_equal(out, m.predict(req, batch_size=BS)[:4])
    snap = eng.stats()
    assert snap["requests"] == 1 and snap["expired"] == 1
    assert snap["submitted"] == 2


def test_engine_split_request_expiry_is_atomic():
    """Partial expiry of a split oversize request resolves the logical
    future ONCE with DeadlineExceeded, counts ONE expired request, and
    the surviving sibling chunks are dropped before packing — zero
    dispatches burned on a request nobody is waiting on."""
    clk = FakeClock()
    m = _model()
    eng = ServingEngine(m, stats_every=0, max_batch=4, max_wait_ms=0.0,
                        clock=clk)
    fut = eng.submit(_requests([10], seed=3)[0], deadline_ms=5.0)
    clk.t = 0.010
    eng.start()
    with pytest.raises(DeadlineExceeded):
        fut.result(timeout=30)
    snap = eng.stats()
    assert snap["expired"] == 1            # logical request, not chunks
    assert snap["dispatches"] == 0         # no sibling burned a dispatch
    eng.stop()


def test_engine_reject_policy_raises_overload_and_counts():
    m = _model()
    eng = ServingEngine(m, stats_every=0, max_batch=4, max_wait_ms=1e6,
                        max_queue_rows=8, admission="reject")
    reqs = _requests([4, 4, 2], seed=4)
    futs = [eng.submit(r) for r in reqs[:2]]   # queued: bound reached
    with pytest.raises(OverloadError, match="rejected"):
        eng.submit(reqs[2])
    assert eng.stats()["rejected"] == 1
    eng.start()
    outs = [f.result(timeout=30) for f in futs]  # queued work still serves
    eng.stop()
    want = m.predict(np.concatenate(reqs[:2]), batch_size=BS)
    np.testing.assert_array_equal(np.concatenate(outs), want[:8])
    snap = eng.stats()
    assert snap["requests"] == 2 and snap["rejected"] == 1
    # every submitted request accounted for exactly once
    assert snap["submitted"] == 3 and snap["peak_queue_rows"] <= 8


def test_engine_shed_oldest_policy_fails_oldest_future():
    m = _model()
    eng = ServingEngine(m, stats_every=0, max_batch=4, max_wait_ms=1e6,
                        max_queue_rows=8, admission="shed_oldest")
    reqs = _requests([4, 4, 4], seed=5)
    doomed = eng.submit(reqs[0])
    kept = eng.submit(reqs[1])
    newest = eng.submit(reqs[2])           # sheds `doomed`
    with pytest.raises(SheddedError, match="shed after queueing"):
        doomed.result(timeout=5)
    eng.start()
    out1 = kept.result(timeout=30)
    out2 = newest.result(timeout=30)
    eng.stop()
    np.testing.assert_array_equal(
        out1, m.predict(reqs[1], batch_size=BS)[:4])
    np.testing.assert_array_equal(
        out2, m.predict(reqs[2], batch_size=BS)[:4])
    snap = eng.stats()
    assert snap["shed"] == 1 and snap["requests"] == 2
    assert snap["peak_queue_rows"] <= 8 and snap["submitted"] == 3


def test_engine_drain_not_started_fails_stragglers_typed():
    m = _model()
    eng = ServingEngine(m, stats_every=0)
    futs = [eng.submit(r) for r in _requests([3, 4], seed=6)]
    assert eng.health == "starting"
    snap = eng.drain(timeout=0)
    for f in futs:
        with pytest.raises(SheddedError, match="drained"):
            f.result(timeout=5)
    assert snap["shed"] == 2
    assert eng.health == "stopped"
    # draining stopped admissions for good — and the refusal is the
    # TYPED admission error, so `except ServingError` clients catch it
    with pytest.raises(OverloadError, match="not admitting"):
        eng.submit(_requests([2], seed=7)[0])


def test_engine_drain_flushes_queue_then_stops():
    m = _model()
    # max_wait so large the queue only ever flushes because drain
    # closed the batcher — the flush is drain's doing, not the timer's
    eng = ServingEngine(m, stats_every=0, max_wait_ms=1e6)
    eng.start()
    req = _requests([5], seed=8)[0]
    fut = eng.submit(req)
    snap = eng.drain(timeout=30)
    np.testing.assert_array_equal(
        fut.result(timeout=5), m.predict(req, batch_size=BS)[:5])
    assert snap["requests"] == 1 and snap["shed"] == 0
    assert eng.health == "stopped"
    # idempotent: a second drain/stop is a no-op
    eng.drain(timeout=0)
    eng.stop()


def test_engine_health_walks_degraded_and_recovers(capsys):
    m = _model()
    eng = ServingEngine(m, stats_every=0, degraded_after_errors=2)
    assert eng.health == "starting"
    boom = {"left": 2}
    orig = m.forward_compiled

    def flaky(bucket):
        if boom["left"] > 0:
            boom["left"] -= 1
            raise RuntimeError("injected dispatch failure")
        return orig(bucket)

    m.forward_compiled = flaky
    try:
        eng.start()
        assert eng.health == "serving"
        r1, r2, r3 = _requests([2, 3, 4], seed=9)
        with pytest.raises(RuntimeError):
            eng.submit(r1).result(timeout=30)
        assert eng.health == "serving"      # one error < threshold
        with pytest.raises(RuntimeError):
            eng.submit(r2).result(timeout=30)
        assert eng.health == "degraded"     # 2 consecutive errors
        out = eng.submit(r3).result(timeout=30)
        assert eng.health == "serving"      # success resets the streak
    finally:
        m.forward_compiled = orig
        eng.stop()
    assert eng.health == "stopped"
    np.testing.assert_array_equal(
        out, m.predict(r3, batch_size=BS)[:4])
    events = [json.loads(l) for l in capsys.readouterr().out.splitlines()
              if l.startswith("{")]
    health = [(e["prev"], e["state"]) for e in events
              if e.get("event") == "serve_health"]
    assert ("serving", "degraded") in health
    assert ("degraded", "serving") in health
    assert health[-1][1] == "stopped"


def test_engine_stats_report_live_queue_depth():
    """The wedged-dispatcher bug: depth used to freeze at the LAST
    dispatch, so a stalled engine behind a growing queue looked
    healthy.  stats() must report the batcher's live count."""
    m = _model()
    eng = ServingEngine(m, stats_every=0)   # not started: no dispatches
    for r in _requests([2, 3, 4], seed=10):
        eng.submit(r)
    snap = eng.stats()
    assert snap["queue_depth"] == 3         # live, despite 0 dispatches
    assert snap["last_dispatch_age_s"] is None
    eng.start()
    # served: the live view drains back to 0
    while eng.stats()["requests"] < 3:
        pass
    assert eng.stats()["queue_depth"] == 0
    assert eng.stats()["last_dispatch_age_s"] is not None
    eng.stop()


def test_metrics_last_dispatch_age_tracks_stall():
    clk = FakeClock()
    sm = ServingMetrics(window_s=100.0, clock=clk,
                        queue_depth_fn=lambda: 7)
    assert sm.snapshot()["last_dispatch_age_s"] is None
    sm.record_dispatch(rows=4, bucket=4, n_reqs=1, queue_depth=0,
                       dispatch_s=0.001)
    clk.t = 5.0
    snap = sm.snapshot()
    assert snap["last_dispatch_age_s"] == pytest.approx(5.0)
    assert snap["queue_depth"] == 7         # live fn wins over last-dispatch
    json.dumps(snap)                        # still one parseable line


def test_submit_names_input_on_uncoercible_payload():
    m = _model()
    eng = ServingEngine(m, stats_every=0)
    # ragged rows: np.array would raise its opaque inhomogeneous-shape
    # error; the engine must name the input and the expected dtype
    with pytest.raises(ValueError, match=r"input 0: cannot coerce"):
        eng.submit([[1.0] * NFEAT, [2.0]])
    # ...and a wrong trailing shape names the input index too
    with pytest.raises(ValueError, match=r"input 0: request rows"):
        eng.submit(np.zeros((2, NFEAT + 1), np.float32))
    eng.stop()


def test_engine_deadline_latency_tracked_separately():
    m = _model()
    with ServingEngine(m, stats_every=0) as eng:
        eng.submit(_requests([3], seed=12)[0],
                   deadline_ms=60_000.0).result(timeout=30)
        eng.submit(_requests([2], seed=13)[0]).result(timeout=30)
    snap = eng.stats()
    assert snap["requests"] == 2
    assert snap["deadline_p99_ms"] is not None  # the deadlined one
    assert snap["expired"] == 0


# ----------------------------------------------------------------------
# FF_FAULT serving kinds (scripts/fault_matrix.sh runs this class)
# ----------------------------------------------------------------------
class TestServeFaults:
    @pytest.fixture
    def arm(self, monkeypatch):
        def _arm(spec):
            monkeypatch.setenv("FF_FAULT", spec)
            faults.reset()
        yield _arm
        monkeypatch.delenv("FF_FAULT", raising=False)
        faults.reset()

    def test_parse_serve_kinds(self):
        specs = faults.parse_faults(
            "serve_slow_dispatch:3,ms=20;serve_fail_dispatch:2,every=4;"
            "serve_queue_spike:1,rows=128")
        assert [s.kind for s in specs] == ["serve_slow_dispatch",
                                          "serve_fail_dispatch",
                                          "serve_queue_spike"]
        assert specs[0].extras["ms"] == "20"
        assert specs[1].extras["every"] == "4"
        assert specs[2].extras["rows"] == "128"
        with pytest.raises(ValueError, match=">= 1"):
            faults.parse_faults("serve_queue_spike:1,rows=0")
        with pytest.raises(ValueError, match=">= 0"):
            # a negative stall would convert slow dispatches into
            # dispatch FAILURES at fire time (sleep raises) — fail at
            # parse, like every other qualifier
            faults.parse_faults("serve_slow_dispatch:1,ms=-5")
        with pytest.raises(ValueError, match="integer"):
            faults.parse_faults("serve_fail_dispatch:soon")

    def test_serve_fail_dispatch_fails_batch_and_recovers(self, arm):
        arm("serve_fail_dispatch:1")
        m = _model()
        eng = ServingEngine(m, stats_every=0)
        doomed = eng.submit(_requests([3], seed=20)[0])
        eng.start()
        with pytest.raises(RuntimeError,
                           match="injected serve dispatch failure"):
            doomed.result(timeout=30)
        req = _requests([4], seed=21)[0]
        out = eng.submit(req).result(timeout=30)   # fault spent: serves
        eng.stop()
        np.testing.assert_array_equal(
            out, m.predict(req, batch_size=BS)[:4])
        snap = eng.stats()
        assert snap["errors"] == 1 and snap["requests"] == 1

    def test_serve_slow_dispatch_uses_injected_sleep(self, arm):
        arm("serve_slow_dispatch:2,ms=7")
        stalls = []
        m = _model()
        eng = ServingEngine(m, stats_every=0, max_wait_ms=0.0,
                            sleep=stalls.append)
        with eng:
            for s in (2, 3, 4):               # three separate dispatches
                eng.submit(_requests([s], seed=s)[0]).result(timeout=30)
        assert stalls == [0.007, 0.007]       # first N dispatches only
        assert eng.stats()["dispatches"] == 3

    def test_serve_queue_spike_exercises_admission(self, arm):
        arm("serve_queue_spike:0,rows=12")
        m = _model()
        eng = ServingEngine(m, stats_every=0, max_batch=4,
                            max_wait_ms=0.0, max_queue_rows=8,
                            admission="shed_oldest")
        req = _requests([2], seed=22)[0]
        fut = eng.submit(req)
        eng.start()
        out = fut.result(timeout=30)          # client request survives
        eng.stop()                            # drains the spike rows
        np.testing.assert_array_equal(
            out, m.predict(req, batch_size=BS)[:2])
        snap = eng.stats()
        assert snap["requests"] == 1          # spike rows are not clients
        # the 12-row spike overflowed the 8-row bound through the real
        # admission path: the bound held and at least one spike chunk
        # was shed
        assert snap["peak_queue_rows"] <= 8
        assert snap["shed"] >= 1


# ----------------------------------------------------------------------
# concurrency smoke: N threads submitting, no interleaving corruption
# ----------------------------------------------------------------------
def test_concurrent_submitters_resolve_correctly():
    m = _model()
    nthreads, per_thread = 6, 12
    rng = np.random.default_rng(7)
    inputs = {t: [rng.standard_normal((int(s), NFEAT)).astype(np.float32)
                  for s in rng.integers(1, 9, per_thread)]
              for t in range(nthreads)}
    expected = {t: m.predict(np.concatenate(inputs[t]), batch_size=BS)
                for t in range(nthreads)}
    results = {}
    with ServingEngine(m, stats_every=0) as eng:
        def worker(t):
            futs = [eng.submit(x) for x in inputs[t]]
            results[t] = [f.result(timeout=60) for f in futs]

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(nthreads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    for t in range(nthreads):
        off = 0
        for x, o in zip(inputs[t], results[t]):
            np.testing.assert_array_equal(
                o, expected[t][off:off + len(x)],
                err_msg=f"thread {t} request at row {off}")
            off += len(x)


# ----------------------------------------------------------------------
# AOT executables: startup warm, cache reuse, predict reroute
# ----------------------------------------------------------------------
def test_forward_compiled_cached_and_shared_with_predict():
    m = _model()
    c8 = m.forward_compiled(8)
    assert m.forward_compiled(8) is c8            # cached per bucket
    x = np.zeros((10, NFEAT), np.float32)
    m.predict(x, batch_size=4)
    # predict shares the (bucket, exec_digest)-keyed cache
    assert (4, m.exec_digest()) in m._fwd_compiled
    assert 4 in m._dummy_labels                   # label feed cached per bs
    with pytest.raises(ValueError, match="bucket batch size"):
        m.forward_compiled(0)


def test_predict_coerces_input_dtype():
    """The old per-call jit silently retraced for an int feed to a
    float-declared input; the AOT reroute must keep that working by
    casting to the declared dtype up front."""
    m = _model()
    x = np.arange(5 * NFEAT, dtype=np.int32).reshape(5, NFEAT)
    out = m.predict(x, batch_size=4)
    want = m.predict(x.astype(np.float32), batch_size=4)
    np.testing.assert_array_equal(out, want)


def test_predict_unchanged_by_reroute():
    m = _model()
    x = np.asarray(_requests([2 * BS + 3], seed=3)[0])
    full = m.predict(x, batch_size=BS)            # exact + padded tail
    again = m.predict(x, batch_size=2 * BS + 3)   # one exact batch
    np.testing.assert_array_equal(full, again)
    exact = m.predict(x[:2 * BS], batch_size=BS)  # n % bs == 0: no pad
    np.testing.assert_array_equal(exact, full[:2 * BS])


# ----------------------------------------------------------------------
# metrics: rolling window, nearest-rank percentiles, JSON events
# ----------------------------------------------------------------------
def test_quantiles_nearest_rank():
    from flexflow_tpu.profiling import quantiles
    q = quantiles([ms / 1e3 for ms in range(1, 101)])
    assert q[0.5] == pytest.approx(0.050)
    assert q[0.95] == pytest.approx(0.095)
    assert q[0.99] == pytest.approx(0.099)
    assert all(np.isnan(v) for v in quantiles([]).values())
    assert quantiles([0.7])[0.99] == pytest.approx(0.7)


def test_serving_metrics_snapshot():
    clk = FakeClock()
    sm = ServingMetrics(window_s=100.0, clock=clk)
    for ms in range(1, 101):
        sm.record_request(ms / 1e3)
    sm.record_dispatch(rows=12, bucket=16, n_reqs=3, queue_depth=2,
                       dispatch_s=0.004)
    sm.record_dispatch(rows=16, bucket=16, n_reqs=4, queue_depth=0,
                       dispatch_s=0.002)
    clk.t = 10.0
    snap = sm.snapshot()
    assert snap["p50_ms"] == pytest.approx(50.0)
    assert snap["p95_ms"] == pytest.approx(95.0)
    assert snap["p99_ms"] == pytest.approx(99.0)
    # qps counts LOGICAL requests (the latency population), not chunks
    assert snap["qps"] == pytest.approx(10.0)         # 100 reqs / 10s
    assert snap["rows_per_sec"] == pytest.approx(2.8)
    assert snap["batch_occupancy"] == pytest.approx((12 / 16 + 1.0) / 2)
    assert snap["queue_depth"] == 0
    assert snap["dispatch_ms"] == pytest.approx(3.0)
    assert snap["dispatches"] == 2 and snap["requests"] == 100


def test_serving_metrics_per_bucket_percentiles():
    """Per-shape-bucket dispatch_ms percentiles (ISSUE 7 satellite): a
    global mean hides which bucket executables are slow, and the
    per-bucket medians are what the calibration harvest
    (search.calibration.harvest_serve_dispatch) consumes."""
    import json as _json
    clk = FakeClock()
    sm = ServingMetrics(window_s=100.0, clock=clk)
    for ms in (2.0, 4.0, 6.0):
        sm.record_dispatch(rows=4, bucket=4, n_reqs=1, queue_depth=0,
                           dispatch_s=ms / 1e3)
    sm.record_dispatch(rows=7, bucket=8, n_reqs=2, queue_depth=0,
                       dispatch_s=0.010)
    snap = sm.snapshot()
    pb = snap["per_bucket"]
    assert set(pb) == {"4", "8"}
    assert pb["4"]["dispatches"] == 3 and pb["4"]["rows"] == 12
    assert pb["4"]["dispatch_p50_ms"] == pytest.approx(4.0)
    assert pb["4"]["dispatch_p99_ms"] == pytest.approx(6.0)
    assert pb["8"]["dispatch_p50_ms"] == pytest.approx(10.0)
    _json.loads(_json.dumps(snap))  # JSON-safe for the serve_stats event
    # ...and the calibration harvest consumes exactly this shape
    from flexflow_tpu.search.calibration import (CalibrationTable,
                                                 harvest_serve_dispatch)
    t = CalibrationTable()
    assert harvest_serve_dispatch(t, "m", snap) == 2
    assert t.dispatch["serve|m|bucket4"]["measured_ms"] == \
        pytest.approx(4.0)


def test_metrics_window_trims_old_samples():
    import json as _json
    clk = FakeClock()
    sm = ServingMetrics(window_s=5.0, clock=clk)
    sm.record_dispatch(rows=8, bucket=8, n_reqs=2, queue_depth=0,
                       dispatch_s=0.001)
    sm.record_request(0.003)
    clk.t = 100.0  # far past the window
    snap = sm.snapshot()
    assert snap["qps"] == 0.0 and snap["batch_occupancy"] == 0.0
    assert snap["dispatches"] == 1  # lifetime totals survive the trim
    # empty latency window reports null, never NaN (bare NaN is not
    # valid JSON and would break the one-parseable-line contract)
    assert snap["p50_ms"] is None and snap["p99_ms"] is None
    _json.loads(_json.dumps(snap))


def test_stop_before_start_fails_queued_futures():
    """stop() on a never-started engine has no dispatcher to drain the
    queue — queued futures must fail loudly, not block forever."""
    m = _model()
    eng = ServingEngine(m, stats_every=0)
    fut = eng.submit(np.zeros((2, NFEAT), np.float32))
    eng.stop()
    with pytest.raises(RuntimeError, match="before it was started"):
        fut.result(timeout=5)


def test_engine_single_use_lifecycle():
    m = _model()
    eng = ServingEngine(m, stats_every=0)
    with eng:
        eng.submit(np.zeros((2, NFEAT), np.float32)).result(timeout=30)
    eng.stop()  # idempotent
    with pytest.raises(RuntimeError, match="stopped"):
        eng.start()
    # a fresh engine on the same model starts warm (shared AOT cache)
    eng2 = ServingEngine(m, stats_every=0)
    with eng2:
        eng2.submit(np.zeros((2, NFEAT), np.float32)).result(timeout=30)


def test_predict_rejects_wrong_input_count():
    m = _model()
    with pytest.raises(ValueError, match="input"):
        m.predict([np.zeros((4, NFEAT), np.float32),
                   np.zeros((4, NFEAT), np.float32)])


def test_engine_emits_serve_stats_events(capsys):
    m = _model()
    with ServingEngine(m, stats_every=1) as eng:
        eng.submit(np.zeros((3, NFEAT), np.float32)).result(timeout=30)
    events = [json.loads(line)
              for line in capsys.readouterr().out.splitlines()
              if line.startswith("{")]
    stats = [e for e in events if e.get("event") == "serve_stats"]
    assert stats, "no serve_stats event emitted"
    for key in ("qps", "rows_per_sec", "batch_occupancy", "queue_depth",
                "p50_ms", "p95_ms", "p99_ms", "dispatches"):
        assert key in stats[-1], key
    assert stats[-1]["final"] is True  # stop() emits the final snapshot
