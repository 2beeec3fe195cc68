"""Observability plane tests (ISSUE 13, docs/observability.md):
request-scoped span tracing with exact counter reconciliation, the
flight recorder's trigger/dump/CLI surface, the metrics registry +
Prometheus exposition + scrape endpoint, and the engine==predict
parity pin with tracing enabled at sample_rate=1.0.
"""

import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu import faults
from flexflow_tpu.obs.flight import (FlightRecorder, get_flight,
                                     validate_flight_dump)
from flexflow_tpu.obs.registry import (MetricsRegistry, get_registry,
                                       start_metrics_server,
                                       validate_prometheus_text)
from flexflow_tpu.obs.trace import (Tracer, get_tracer, to_chrome,
                                    validate_chrome_trace,
                                    validate_raw_trace)
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.serving import ServingEngine

BS = 16
NFEAT = 12
NCLS = 5


@pytest.fixture
def tracer():
    """The process tracer, enabled at 1.0 and cleaned up after."""
    tr = get_tracer()
    tr.reset()
    tr.configure(sample_rate=1.0)
    yield tr
    tr.disable()
    tr.reset()


def _model(max_batch=BS):
    cfg = ff.FFConfig(batch_size=BS, compute_dtype="float32")
    cfg.serve_max_batch = max_batch
    m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
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


# ----------------------------------------------------------------------
# tracer unit behavior
# ----------------------------------------------------------------------
def test_tracer_off_allocates_nothing():
    tr = Tracer()
    assert tr.active is False
    assert tr.new_trace() is None
    tr.span("x", "t1", 0.0, 1.0)  # dropped silently while off
    assert tr.snapshot()["spans"] == []


def test_tracer_systematic_sampling_exact_fraction():
    tr = Tracer()
    tr.configure(sample_rate=0.25)
    ids = [tr.new_trace() for _ in range(100)]
    assert sum(1 for i in ids if i is not None) == 25
    # deterministic: the same run samples the same requests
    tr2 = Tracer()
    tr2.configure(sample_rate=0.25)
    ids2 = [tr2.new_trace() for _ in range(100)]
    assert [i is None for i in ids] == [i is None for i in ids2]
    with pytest.raises(ValueError, match="0, 1"):
        tr.configure(sample_rate=1.5)


def test_tracer_ring_bounded_and_dropped_counted():
    tr = Tracer(capacity=8)
    tr.configure(sample_rate=1.0)
    for i in range(20):
        tr.span("s", None, float(i), float(i) + 0.5)
    snap = tr.snapshot()
    assert len(snap["spans"]) == 8
    assert snap["dropped"] == 12
    # the ring keeps the NEWEST spans
    assert snap["spans"][-1]["t0_ns"] == int(19e9)


def test_raw_and_chrome_validation_round_trip():
    tr = Tracer()
    tr.configure(sample_rate=1.0)
    t = tr.new_trace()
    tr.span("queue", t, 0.001, 0.002, tid="m")
    tr.span("request", t, 0.001, 0.003, phase="completed")
    raw = tr.snapshot()
    assert validate_raw_trace(raw) == []
    chrome = to_chrome(raw)
    assert validate_chrome_trace(chrome) == []
    ev = chrome["traceEvents"]
    assert len(ev) == 2 and ev[0]["ph"] == "X"
    assert ev[1]["args"]["trace_id"] == t
    # microseconds: 1ms span -> dur 1000us
    assert ev[0]["dur"] == pytest.approx(1000.0)
    # invalid cases are named, not crashed on
    assert validate_raw_trace({"schema": "nope", "spans": []})
    assert validate_raw_trace({"schema": "ff-trace-v1",
                               "spans": [{"name": "request",
                                          "t0_ns": 0, "t1_ns": 1,
                                          "args": {"phase": "bogus"}}]})
    bad = json.loads(json.dumps(chrome))
    bad["traceEvents"][0].pop("ts")
    assert validate_chrome_trace(bad)


def test_trace_export_cli_round_trip(tmp_path, tracer, capsys):
    from flexflow_tpu.obs.trace import trace_main
    t = tracer.new_trace()
    tracer.span("request", t, 0.0, 0.5, phase="completed")
    raw_path = str(tmp_path / "raw.json")
    tracer.save(raw_path)
    out_path = str(tmp_path / "chrome.json")
    assert trace_main(["export", raw_path, "--out", out_path]) == 0
    with open(out_path) as f:
        assert validate_chrome_trace(json.load(f)) == []
    assert trace_main(["summary", raw_path]) == 0
    summary = json.loads(capsys.readouterr().out.strip())
    assert summary["terminal_phases"] == {"completed": 1}
    # corrupt file -> exit 1 with the problem named
    bad = tmp_path / "bad.json"
    bad.write_text('{"schema": "ff-trace-v1", "spans": [{}]}')
    assert trace_main(["export", str(bad)]) == 1
    assert trace_main(["export", str(tmp_path / "missing.json")]) == 2


# ----------------------------------------------------------------------
# metrics registry + exposition + scrape endpoint
# ----------------------------------------------------------------------
def test_registry_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    c = reg.counter("ff_test_total", "help text", ("model",))
    c.labels(model="a").inc(3)
    c.labels(model="b").inc()
    g = reg.gauge("ff_test_depth", "live depth")
    g.labels().set_fn(lambda: 7)
    # tiny values render with negative exponents (repr(4.5e-05)) and
    # must stay parseable — the committed --prom-out artifact would
    # otherwise trip the CI gate the first time one appears
    reg.counter("ff_test_tiny_total", "tiny").labels().inc(4.5e-05)
    h = reg.histogram("ff_test_lat_seconds", "latency", (),
                      buckets=(0.1, 1.0))
    h.labels().observe(0.05)
    h.labels().observe(0.5)
    h.labels().observe(5.0)
    text = reg.render()
    assert "ff_test_tiny_total 4.5e-05" in text
    assert 'ff_test_total{model="a"} 3' in text
    assert 'ff_test_total{model="b"} 1' in text
    assert "ff_test_depth 7" in text
    assert 'ff_test_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'ff_test_lat_seconds_bucket{le="1"} 2' in text
    assert 'ff_test_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "ff_test_lat_seconds_count 3" in text
    assert validate_prometheus_text(text) == []
    # family totals sum across children
    assert c.total() == 4
    # idempotent re-declare, type conflict rejected
    assert reg.counter("ff_test_total", "help text", ("model",)) is c
    with pytest.raises(ValueError, match="already declared"):
        reg.gauge("ff_test_total", "x", ("model",))
    with pytest.raises(ValueError, match="wants labels"):
        c.labels(tenant="a")


def test_prometheus_validator_catches_defects():
    assert validate_prometheus_text("garbage line here\n")
    assert validate_prometheus_text("ff_x 1\n")  # no TYPE
    # histogram whose +Inf bucket disagrees with _count
    bad = ("# TYPE ff_h histogram\n"
           'ff_h_bucket{le="+Inf"} 2\n'
           "ff_h_sum 1\n"
           "ff_h_count 3\n")
    probs = validate_prometheus_text(bad)
    assert any("+Inf" in p for p in probs)


def test_metrics_http_endpoint_scrapes():
    reg = MetricsRegistry()
    reg.counter("ff_scrape_total", "scrapes").labels().inc(2)
    server = start_metrics_server(0, host="127.0.0.1", registry=reg)
    try:
        port = server.server_port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert "ff_scrape_total 2" in body
        assert validate_prometheus_text(body) == []
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/other", timeout=10)
    finally:
        server.shutdown()
        server.server_close()


def test_engine_stop_releases_registry_hooks():
    """A stopped engine must not be retained by the process-global
    registry: stop() freezes the live queue-depth gauge and drops the
    provider closure (the path to the batcher, and through it the
    model); lifetime counters stay readable for scrape continuity."""
    model = _model()
    eng = ServingEngine(model)
    with eng:
        eng.submit(_requests([4])[0]).result(timeout=120)
    m = eng.metrics
    assert m.queue_depth_fn is None          # closure dropped
    assert m._ctr["queue_depth"]._fn is None  # gauge frozen
    assert m.total_requests == 1             # counters still readable
    m.release()                              # idempotent
    assert m.total_requests == 1


def test_metrics_unregister_reclaims_series():
    """unregister() removes an engine generation's label series from
    the registry (render/total) while its direct children keep
    working — the fleet's bounded-retirement scheme depends on both
    halves (a week of hot swaps must not grow /metrics forever)."""
    from flexflow_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(model="ephemeral")
    m.record_submitted()
    needle = f'ff_serve_submitted_total{{model="ephemeral",eng="{m.eng_id}"}}'
    assert needle in get_registry().render()
    m.unregister()
    assert needle not in get_registry().render()
    # direct reads (the fleet's live retired fold) still work
    assert m.total_submitted == 1
    m.record_submitted()   # straggler record: safe, just unexposed
    assert m.total_submitted == 2


def test_fleet_swap_retirement_bounded():
    """Hot-swapping one tenant many times keeps the registry bounded:
    at most _MAX_RETIRED_METRICS retired generations stay live, older
    ones fold into the static carry — and the tenant's lifetime
    counters stay EXACT across every generation."""
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        from test_fleet import _dense_builder, _rows
    finally:
        sys.path.pop(0)
    from flexflow_tpu.fflogger import silenced
    from flexflow_tpu.serving.fleet import FleetEngine, ModelRegistry
    from flexflow_tpu.serving.fleet.engine import _MAX_RETIRED_METRICS
    reg = ModelRegistry()
    # unique tenant name: the process registry is shared across the
    # test session, and other suites register model="a" engines whose
    # series legitimately persist
    reg.register("swapper", _dense_builder(24, seed=1), batch_size=8)
    swaps = _MAX_RETIRED_METRICS + 3
    with silenced("serve"), FleetEngine(reg) as fleet:
        xs = _rows(4)
        total = 0
        for _ in range(swaps):
            fleet.submit("swapper", xs).result(timeout=60)
            total += 1
            fleet.load("swapper", wait=True)
        fleet.submit("swapper", xs).result(timeout=60)
        total += 1
        t = fleet._tenant("swapper")
        assert len(t.retired) <= _MAX_RETIRED_METRICS
        snap = fleet.stats("swapper")
        assert snap["requests"] == total == snap["submitted"]
        assert snap["engine_generation"] == len(t.retired)
    # the folded generations' series are gone from the exposition...
    text = get_registry().render()
    live_engs = {t.engine.metrics.eng_id} | {m.eng_id for m in t.retired}
    import re as _re
    series = _re.findall(
        r'ff_serve_submitted_total\{model="swapper",eng="(\d+)"\}',
        text)
    assert set(series) <= live_engs
    # ...but their counts MOVED into the tenant's eng="carry" series:
    # the scraped per-model sum stays monotonic and equals stats()
    vals = _re.findall(
        r'ff_serve_submitted_total\{model="swapper",eng="[^"]+"\} (\d+)',
        text)
    assert sum(int(v) for v in vals) == total


def test_serving_metrics_are_views_over_registry():
    """The serve_stats numbers and the registry children are the SAME
    counters: incrementing through the metrics API moves the rendered
    exposition, and two engines with one model tag stay separate."""
    from flexflow_tpu.serving.metrics import ServingMetrics
    m1 = ServingMetrics(model="twin")
    m2 = ServingMetrics(model="twin")
    m1.record_submitted()
    m1.record_request(0.01)
    m2.record_submitted()
    m2.record_rejected()
    assert (m1.snapshot()["requests"], m1.snapshot()["rejected"]) == (1, 0)
    assert (m2.snapshot()["requests"], m2.snapshot()["rejected"]) == (0, 1)
    text = get_registry().render()
    assert (f'ff_serve_requests_total{{model="twin",eng="{m1.eng_id}"}} 1'
            in text)
    assert (f'ff_serve_rejected_total{{model="twin",eng="{m2.eng_id}"}} 1'
            in text)
    assert validate_prometheus_text(text) == []


# ----------------------------------------------------------------------
# engine tracing end-to-end: spans reconcile with counters, parity holds
# ----------------------------------------------------------------------
def test_engine_spans_reconcile_with_counters(tracer):
    model = _model()
    sizes = [1, 3, BS, BS + 5, 2, 7]      # includes an oversize split
    reqs = _requests(sizes)
    eng = ServingEngine(model)
    with eng:
        outs = [eng.submit(r).result(timeout=120) for r in reqs]
    snap = eng.stats()
    phases = tracer.terminal_phase_counts()
    # EXACT reconciliation: every submitted logical request produced
    # one terminal span whose phase matches the engine counters
    assert phases == {"completed": len(reqs)}
    assert snap["submitted"] == len(reqs) == snap["requests"]
    raw = tracer.snapshot()
    by_name = {}
    for s in raw["spans"]:
        by_name[s["name"]] = by_name.get(s["name"], 0) + 1
    # one queue span per batcher entry (the oversize request split into
    # two chunks), one pack/dispatch/fetch/scatter quartet per dispatch
    assert by_name["queue"] == len(reqs) + 1
    assert (by_name["pack"] == by_name["dispatch"] == by_name["fetch"]
            == by_name["scatter"] == snap["dispatches"])
    assert validate_raw_trace(raw) == []
    # parity with tracing ON at sample_rate=1.0: bit-identical vs
    # predict (the acceptance pin — tracing must not perturb numerics)
    for r, out in zip(reqs, outs):
        want = model.predict(r, batch_size=max(2, r.shape[0]))
        np.testing.assert_array_equal(out, want[:r.shape[0]])


def test_engine_rejected_and_expired_phases_traced(tracer):
    from flexflow_tpu.serving import OverloadError
    model = _model()
    eng = ServingEngine(model, max_queue_rows=BS, admission="reject")
    big = _requests([BS])[0]
    # not started: the queue fills and the next submit rejects
    eng.submit(big)
    with pytest.raises(OverloadError):
        eng.submit(big)
    eng.stop()  # fails the queued request (never started -> shed)
    phases = tracer.terminal_phase_counts()
    assert phases.get("rejected") == 1
    assert phases.get("shed") == 1
    snap = eng.stats()
    assert snap["rejected"] == 1 and snap["shed"] == 1
    assert snap["submitted"] == sum(phases.values()) == 2


def test_cancel_while_queued_reconciles(tracer):
    """A client cancel() on a still-queued request succeeds without
    any resolution path running — the outcome is counted at the cancel
    instant (once), so submitted == terminal spans still holds
    (review finding: this used to leak one per cancel)."""
    model = _model()
    eng = ServingEngine(model)   # not started: requests stay queued
    fut = eng.submit(_requests([4])[0])
    assert fut.cancel()
    eng.stop()                   # sweeps the queue; must not re-count
    snap = eng.stats()
    assert snap["cancelled"] == 1 and snap["submitted"] == 1
    phases = tracer.terminal_phase_counts()
    assert phases == {"cancelled": 1}

    # generation: cancel a queued prompt swept by stop()
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        from test_generation import _build_lm
    finally:
        sys.path.pop(0)
    from flexflow_tpu.serving.generation import GenerationEngine
    tracer.reset()
    tracer.configure(sample_rate=1.0)
    lm = _build_lm()
    gen = GenerationEngine(lm, slots=2, max_new_tokens=4)
    stream = gen.submit(np.asarray([1, 2, 3], np.int32))
    stream.cancel()
    gen.stop()
    gsnap = gen.stats()
    assert gsnap["cancelled"] == 1 and gsnap["submitted"] == 1
    assert tracer.terminal_phase_counts() == {"cancelled": 1}


def test_generation_engine_spans_reconcile(tracer):
    sys.path.insert(0, os.path.dirname(__file__))
    try:
        from test_generation import _build_lm
    finally:
        sys.path.pop(0)
    from flexflow_tpu.serving.generation import GenerationEngine
    lm = _build_lm()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 61, 4).astype(np.int32) for _ in range(3)]
    eng = GenerationEngine(lm, slots=2, max_new_tokens=4)
    with eng:
        streams = [eng.submit(p) for p in prompts]
        for s in streams:
            s.result(timeout=120)
    phases = tracer.terminal_phase_counts()
    assert phases == {"completed": len(prompts)}
    names = {s["name"] for s in tracer.snapshot()["spans"]}
    # the generation span vocabulary: queue wait, prefill (TTFT), the
    # per-step decode dispatch, and the terminal request span
    assert {"queue", "prefill", "decode_step", "request"} <= names
    snap = eng.stats()
    assert snap["requests"] == len(prompts)
    assert snap["submitted"] == sum(phases.values())


def test_fit_records_train_window_spans(tracer):
    cfg = ff.FFConfig(batch_size=8, compute_dtype="float32",
                      steps_per_dispatch=2)
    model = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
    x = model.create_tensor((8, 6), name="x")
    t = model.dense(x, 4)
    model.compile(ff.SGDOptimizer(lr=0.1),
                  "sparse_categorical_crossentropy", ["accuracy"],
                  final_tensor=t)
    model.init_layers(seed=0)
    rng = np.random.default_rng(0)
    model.fit(rng.standard_normal((32, 6), dtype=np.float32),
              rng.integers(0, 4, (32, 1)).astype(np.int32),
              epochs=1, verbose=False)
    spans = [s for s in tracer.snapshot()["spans"]
             if s["name"] == "train_window"]
    # 32 samples / batch 8 / K=2 -> 2 windows, each spanning 2 steps
    assert len(spans) == 2
    assert all(s["cat"] == "train" and s["args"]["steps"] == 2
               for s in spans)
    assert len({s["trace"] for s in spans}) == 1  # one trace per fit()
    # the train loop fed the registry too
    text = get_registry().render()
    assert "ff_train_steps_total" in text


# ----------------------------------------------------------------------
# flight recorder: ring, triggers, dumps, CLI
# ----------------------------------------------------------------------
def test_flight_ring_bounded_and_dump_schema(tmp_path):
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record_event({"cat": "x", "event": "epoch", "i": i})
    assert len(rec.snapshot()) == 4
    assert rec.snapshot()[-1]["i"] == 9
    path = rec.dump("unit_test", directory=str(tmp_path))
    assert path and os.path.exists(path)
    with open(path) as f:
        obj = json.load(f)
    assert validate_flight_dump(obj) == []
    assert obj["reason"] == "unit_test" and len(obj["records"]) == 4
    # rate-limited: an immediate second dump for the same reason skips
    assert rec.dump("unit_test", directory=str(tmp_path)) is None
    assert rec.dump("unit_test", directory=str(tmp_path),
                    force=True) is not None
    # no directory -> recorder-only mode, nothing written
    assert rec.dump("unit_test") is None or os.environ.get(
        "FF_FLIGHT_DIR")


def test_flight_taps_capture_events_and_spans(tracer):
    from flexflow_tpu.fflogger import get_logger
    flight = get_flight()
    get_logger("serve").event("serve_drain", model="tapped",
                              timeout_s=0, queue_depth=0,
                              pending_rows=0)
    t = tracer.new_trace()
    tracer.span("request", t, 0.0, 1.0, phase="completed")
    # scan the ring's TAIL, not an index offset: under the full suite
    # the bounded ring may already be at capacity, shifting indices
    recs = flight.snapshot()[-10:]
    assert any(r["kind"] == "event" and r.get("event") == "serve_drain"
               and r.get("model") == "tapped" for r in recs)
    assert any(r["kind"] == "span" and r.get("name") == "request"
               and r.get("trace") == t for r in recs)


def test_flight_excepthook_dumps(tmp_path, monkeypatch):
    import flexflow_tpu.obs.flight as fl
    monkeypatch.setenv("FF_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setattr(fl, "_orig_excepthook", None)
    monkeypatch.setattr(fl, "_orig_thread_hook", None)
    seen = []
    monkeypatch.setattr(sys, "excepthook",
                        lambda *a: seen.append(a), raising=False)
    monkeypatch.setattr(threading, "excepthook",
                        lambda a: seen.append(a), raising=False)
    fl.install_excepthook()
    try:
        raise RuntimeError("boom")
    except RuntimeError:
        sys.excepthook(*sys.exc_info())
    assert len(seen) == 1  # original hook still ran
    dumps = sorted(p for p in os.listdir(str(tmp_path))
                   if p.startswith("flight_fatal_exception"))
    assert len(dumps) == 1
    with open(tmp_path / dumps[0]) as f:
        obj = json.load(f)
    assert obj["extra"]["type"] == "RuntimeError"
    assert obj["extra"]["where"] == "main"
    # a dispatcher DAEMON thread dying routes to threading.excepthook
    # — the most likely serving crash must also leave a post-mortem
    t = threading.Thread(target=lambda: 1 / 0, name="ff-serve-dispatch")
    t.start()
    t.join(30)
    assert len(seen) == 2  # original threading hook still ran
    dumps = sorted(p for p in os.listdir(str(tmp_path))
                   if p.startswith("flight_fatal_exception"))
    assert len(dumps) == 2
    with open(tmp_path / dumps[-1]) as f:
        obj = json.load(f)
    assert obj["extra"]["type"] == "ZeroDivisionError"
    assert obj["extra"]["where"] == "ff-serve-dispatch"


class TestFlightFaults:
    """fault_matrix.sh cases: an injected dispatch failure must leave a
    flight dump naming the failed dispatch, with the failing requests'
    spans retained in the ring (the ISSUE 13 acceptance pin)."""

    @pytest.fixture
    def arm(self, monkeypatch, tmp_path):
        monkeypatch.setenv("FF_FLIGHT_DIR", str(tmp_path))

        def _arm(spec):
            monkeypatch.setenv("FF_FAULT", spec)
            faults.reset()
        yield _arm
        monkeypatch.delenv("FF_FAULT", raising=False)
        faults.reset()

    def test_serve_fail_dispatch_leaves_flight_dump(self, arm, tmp_path,
                                                    tracer):
        arm("serve_fail_dispatch:1")
        model = _model()
        eng = ServingEngine(model)
        with eng:
            fut = eng.submit(_requests([4])[0])
            with pytest.raises(RuntimeError, match="injected"):
                fut.result(timeout=120)
            # the engine keeps serving after the poisoned dispatch
            ok = eng.submit(_requests([2], seed=1)[0]).result(timeout=120)
            assert ok.shape == (2, NCLS)
        dumps = [p for p in os.listdir(str(tmp_path))
                 if p.startswith("flight_serve_dispatch_error")]
        assert len(dumps) == 1, os.listdir(str(tmp_path))
        with open(tmp_path / dumps[0]) as f:
            obj = json.load(f)
        assert validate_flight_dump(obj) == []
        # the dump NAMES the failed dispatch...
        assert "injected serve dispatch failure" in obj["extra"]["error"]
        assert obj["extra"]["failed_requests"] == 1
        events = [r for r in obj["records"] if r["kind"] == "event"
                  and r.get("event") == "serve_dispatch_error"]
        assert events and "injected" in events[0]["error"]
        # ...and retains the failing dispatch's spans: the request's
        # terminal span carries phase=error
        spans = [r for r in obj["records"] if r["kind"] == "span"
                 and r.get("name") == "request"]
        assert any(s["args"]["phase"] == "error" for s in spans)
        # reconciliation holds under the fault too
        assert tracer.terminal_phase_counts() == {"error": 1,
                                                  "completed": 1}

    def test_flight_cli_dump_and_show(self, arm, tmp_path, capsys):
        from flexflow_tpu.obs.flight import flight_main
        arm("serve_fail_dispatch:1")
        model = _model()
        eng = ServingEngine(model)
        with eng:
            with pytest.raises(RuntimeError):
                eng.submit(_requests([4])[0]).result(timeout=120)
        assert flight_main(["dump", "--dir", str(tmp_path)]) == 0
        # the engine's own event lines share stdout; the path is last
        path = capsys.readouterr().out.strip().splitlines()[-1]
        assert os.path.exists(path)
        assert flight_main(["show", path, "--last", "10"]) == 0
        shown = capsys.readouterr().out
        assert "serve_dispatch_error" in shown
        # --last 0 means header only, not "the whole ring"
        assert flight_main(["show", path, "--last", "0"]) == 0
        header_only = capsys.readouterr().out
        assert "showing last 0" in header_only
        assert "[event]" not in header_only and "[span ]" not in \
            header_only
        assert flight_main(["dump", "--dir",
                            str(tmp_path / "empty")]) == 1

    def test_health_degraded_edge_dumps(self, arm, tmp_path):
        # every dispatch fails -> consecutive errors push the engine
        # into `degraded`, which is its own flight trigger
        arm("serve_fail_dispatch:4")
        model = _model()
        eng = ServingEngine(model, degraded_after_errors=2)
        with eng:
            for i in range(3):
                with pytest.raises(RuntimeError):
                    eng.submit(_requests([2], seed=i)[0]).result(
                        timeout=120)
        assert any(p.startswith("flight_health_degraded")
                   for p in os.listdir(str(tmp_path))), \
            os.listdir(str(tmp_path))
