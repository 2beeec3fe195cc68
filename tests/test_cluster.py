"""Disaggregated prefill/decode cluster tests (ISSUE 19,
docs/serving.md "Disaggregated prefill/decode"): the FleetRouter over
role-tagged fleet hosts, KV page-chain migration bit-parity, the
router FF_FAULT kinds (``migrate_fail_at`` / ``route_host_down`` —
zero unaffected streams fail, pools drain to zero on both engines),
route/migrate span reconciliation, the TenantAutoscaler fake-clock
grow/decay cycle, cross-tenant dispatch sharing parity and the FF132
disagg-topology gate.
"""

import os
import time

import numpy as np
import pytest

from flexflow_tpu import faults
from flexflow_tpu.fflogger import capture_events, silenced
from flexflow_tpu.obs.trace import get_tracer
from flexflow_tpu.serving.cluster import FleetRouter
from flexflow_tpu.serving.fleet import (FleetEngine, ModelRegistry,
                                        TenantAutoscaler, fleet_gate_report)
from flexflow_tpu.serving.generation import GenerationEngine
from flexflow_tpu.serving.generation.pages import export_pages, import_pages
from tests.serving_fixtures import (VOCAB, _build_lm, _reconciled,
                                    build_disagg)

SLOTS, MAX_SEQ = 4, 64


@pytest.fixture(scope="module")
def lm():
    with silenced("ff", "serve"):
        return _build_lm(SLOTS, MAX_SEQ, 32, 2, 1, 0)


@pytest.fixture
def tracer():
    tr = get_tracer()
    tr.reset()
    tr.configure(sample_rate=1.0)
    yield tr
    tr.disable()
    tr.reset()


def _prompts(n, seed=3, lo=4, hi=MAX_SEQ // 2):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, int(rng.integers(lo, hi))
                         ).astype(np.int32) for _ in range(n)]


def _tokens(stream, timeout=120):
    return [int(t) for t in stream.result(timeout=timeout)]


def _stop(router, fleets):
    router.stop()
    for f in fleets:
        f.stop()


def _wait(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.005)


def _drained(*engines):
    """Pool accounting after the streams retire: every page freed on
    every engine (the ISSUE 19 fault-matrix acceptance)."""
    _wait(lambda: all(e._pool.pages_in_use == 0 for e in engines))
    return True


# ----------------------------------------------------------------------
# migration bit-parity + pool drain + cross-engine reconciliation
# ----------------------------------------------------------------------
def test_disagg_tokens_bit_identical_and_pools_drain(lm):
    """The migration contract: a stream that prefills on one engine
    and decodes on another emits EXACTLY the co-located tokens (greedy,
    prefix cache on AND off), both pools drain to zero, and submitted
    == terminals summed across the engines."""
    prompts = _prompts(2)
    max_new = 6
    for pc in ("off", "on"):
        eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                               stats_every=0, prefill_chunk=8,
                               prefix_cache=pc)
        with silenced("serve"), eng:
            colo = [_tokens(eng.submit(p, max_new_tokens=max_new))
                    for p in prompts]
        with silenced("serve"):
            router, fleets, (pf_eng, dc_eng) = build_disagg(
                lm, SLOTS, MAX_SEQ, 8, prefix_cache=pc, pf_pace_s=0.0)
        try:
            with silenced("serve"):
                disagg = [_tokens(router.submit("lm", p,
                                                max_new_tokens=max_new))
                          for p in prompts]
            rstats = router.stats()
            assert rstats["routes"] == len(prompts)
            assert _reconciled([pf_eng.stats(), dc_eng.stats()])
            if pc == "off":
                # every stream left the prefill host; nothing held by
                # a prefix trie, so both pools drain to zero
                assert rstats["migrations"] == len(prompts)
                assert rstats["migrated_bytes"] > 0
                assert _drained(pf_eng, dc_eng)
        finally:
            with silenced("serve"):
                _stop(router, fleets)
        assert disagg == colo, f"prefix_cache={pc}"


def test_speculative_decode_composes_with_migration(lm):
    """The tentpole composition clause: a decode host running
    SPECULATIVE decode (draft co-hosted with the decode engine) adopts
    the migrated stream and still emits bit-identical tokens — and it
    really speculated, not silently demoted."""
    prompt = np.arange(1, 7, dtype=np.int32)
    eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                           stats_every=0, prefill_chunk=8,
                           prefix_cache="off")
    with silenced("serve"), eng:
        want = _tokens(eng.submit(prompt, max_new_tokens=8))
    with silenced("serve"):
        pf_eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                                  stats_every=0, prefill_chunk=8,
                                  prefix_cache="off")
        dc_eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                                  stats_every=0, prefix_cache="off",
                                  draft_model=lm, spec_gamma=2)
        pf, dc = FleetEngine(), FleetEngine()
        pf.add_engine("lm", pf_eng)
        dc.add_engine("lm", dc_eng)
        pf.start()
        dc.start()
        router = FleetRouter()
        router.add_host("pf0", pf, role="prefill")
        router.add_host("dc0", dc, role="decode")
        router.start()
    try:
        with silenced("serve"):
            got = _tokens(router.submit("lm", prompt,
                                        max_new_tokens=8))
        assert router.stats()["migrations"] == 1
        snap = dc_eng.stats()
        # an identical-weights draft accepts every greedy window
        assert snap["spec_proposed_tokens"] > 0
        assert snap["spec_accepted_tokens"] == 8
        assert snap["spec_fallbacks"] == 0
        assert _reconciled([pf_eng.stats(), snap])
        assert _drained(pf_eng, dc_eng)
    finally:
        with silenced("serve"):
            _stop(router, (pf, dc))
    assert got == want


# ----------------------------------------------------------------------
# router FF_FAULT kinds — the fault-matrix target class
# (scripts/fault_matrix.sh: zero unaffected streams fail, pools drain)
# ----------------------------------------------------------------------
def _mixed_pair(lm, slots0=2, slots1=2):
    """Two mixed-role hosts over shared weights behind one router."""
    e0 = GenerationEngine(lm, slots=slots0, max_seq=MAX_SEQ,
                          stats_every=0, prefill_chunk=8,
                          prefix_cache="off")
    e1 = GenerationEngine(lm, slots=slots1, max_seq=MAX_SEQ,
                          stats_every=0, prefill_chunk=8,
                          prefix_cache="off")
    f0, f1 = FleetEngine(), FleetEngine()
    f0.add_engine("lm", e0)
    f1.add_engine("lm", e1)
    f0.start()
    f1.start()
    r = FleetRouter()
    r.add_host("m0", f0, role="mixed")
    r.add_host("m1", f1, role="mixed")
    r.start()
    return r, (f0, f1), (e0, e1)


class TestRouterFaults:
    def setup_method(self):
        faults.reset()

    def teardown_method(self):
        os.environ.pop("FF_FAULT", None)
        faults.reset()

    def test_router_fault_grammar(self):
        os.environ["FF_FAULT"] = "migrate_fail_at:2;route_host_down:pf0"
        faults.reset()
        specs = faults.router_faults()
        assert [(s.kind, s.arg) for s in specs] == [
            ("migrate_fail_at", "2"), ("route_host_down", "pf0")]

    def test_migrate_fail_at_falls_back_colocated(self, lm, tmp_path,
                                                  monkeypatch):
        """The Nth migration handoff raises: the stream keeps decoding
        CO-LOCATED with the exact same tokens, one serve_health
        fallback event fires, a flight dump lands, no stream fails,
        both pools drain."""
        monkeypatch.setenv("FF_FLIGHT_DIR", str(tmp_path))
        prompt = np.arange(1, 7, dtype=np.int32)
        eng = GenerationEngine(lm, slots=SLOTS, max_seq=MAX_SEQ,
                               stats_every=0, prefill_chunk=8)
        with silenced("serve"), eng:
            want = _tokens(eng.submit(prompt, max_new_tokens=6))
        os.environ["FF_FAULT"] = "migrate_fail_at:1"
        faults.reset()
        with silenced("serve"):
            router, fleets, (pf_eng, dc_eng) = build_disagg(
                lm, SLOTS, MAX_SEQ, 8, pf_pace_s=0.0)
        try:
            with silenced("serve"), capture_events("serve") as events:
                got = _tokens(router.submit("lm", prompt,
                                            max_new_tokens=6))
            rstats = router.stats()
            pf_snap, dc_snap = pf_eng.stats(), dc_eng.stats()
            assert _drained(pf_eng, dc_eng)
        finally:
            with silenced("serve"):
                _stop(router, fleets)
        assert got == want  # fallback costs the stream NOTHING
        health = [e for e in events if e["event"] == "serve_health"
                  and e.get("component") == "migration"]
        assert len(health) == 1
        assert health[0]["status"] == "fallback"
        assert health[0]["reason"] == "handoff_error"
        assert rstats["migrations"] == 0
        assert rstats["migrate_attempts"] == 1
        # the stream terminated on the SOURCE engine; nothing reached
        # the decode host, nothing errored anywhere
        assert pf_snap["requests"] == 1 and pf_snap["errors"] == 0
        assert dc_snap["submitted"] == 0 and dc_snap["errors"] == 0
        assert _reconciled([pf_snap, dc_snap])
        # the error leg leaves a post-mortem on disk
        dumps = list(tmp_path.iterdir())
        assert dumps and any("gen_migrate_error" in p.read_text()
                             for p in dumps)

    def test_route_host_down_fault_drains_to_survivor(self, lm):
        """``route_host_down:<name>`` fires at the first routing
        decision: every stream routes to the survivor and completes —
        zero failures, the downed host never sees a request."""
        os.environ["FF_FAULT"] = "route_host_down:m0"
        faults.reset()
        with silenced("serve"):
            router, fleets, (e0, e1) = _mixed_pair(lm)
        try:
            with silenced("serve"), capture_events("serve") as events:
                outs = [_tokens(router.submit("lm", p,
                                              max_new_tokens=4))
                        for p in _prompts(3, seed=5)]
            assert all(len(o) == 4 for o in outs)
            assert router.stats()["hosts"]["m0"]["down"] is True
            snap0, snap1 = e0.stats(), e1.stats()
            assert snap0["submitted"] == 0
            assert snap1["requests"] == 3 and snap1["errors"] == 0
            assert _reconciled([snap0, snap1])
            assert _drained(e0, e1)
        finally:
            with silenced("serve"):
                _stop(router, fleets)
        assert "router_host_down" in [e["event"] for e in events]

    def test_mark_down_requeues_queued_streams_to_survivor(self, lm):
        """mark_down with QUEUED work behind occupied slots: the
        queue drains to the survivor (requeue — admitted work is never
        re-judged), the in-flight streams finish where they run, and
        zero streams fail."""
        with silenced("serve"):
            router, fleets, (e0, e1) = _mixed_pair(lm, slots0=2)
        try:
            with silenced("serve"), capture_events("serve") as events:
                f0 = fleets[0]
                # bypass the router so placement is deterministic: s1
                # and s2 occupy both of m0's slots, s3/s4 queue
                s1 = f0.submit("lm", _prompts(1, seed=7)[0],
                               max_new_tokens=32)
                s2 = f0.submit("lm", _prompts(1, seed=8)[0],
                               max_new_tokens=32)
                next(iter(s1))  # both admitted and decoding
                next(iter(s2))
                s3 = f0.submit("lm", _prompts(1, seed=9)[0],
                               max_new_tokens=4)
                s4 = f0.submit("lm", _prompts(1, seed=10)[0],
                               max_new_tokens=4)
                moved = router.mark_down("m0")
                assert moved == {"lm": 2}
                assert len(_tokens(s3)) == 4
                assert len(_tokens(s4)) == 4
                assert len(_tokens(s1)) == 32  # finish on m0
                assert len(_tokens(s2)) == 32
            snap0, snap1 = e0.stats(), e1.stats()
            # s2/s3 submitted on m0, terminal on m1: only the
            # cross-engine sum balances
            assert snap0["errors"] == 0 and snap1["errors"] == 0
            assert snap1["requests"] == 2
            assert _reconciled([snap0, snap1])
            assert _drained(e0, e1)
        finally:
            with silenced("serve"):
                _stop(router, fleets)
        assert "router_host_down" in [e["event"] for e in events]


# ----------------------------------------------------------------------
# observability: route/migrate spans + ff_router_* families
# ----------------------------------------------------------------------
def test_route_and_migrate_spans_reconcile(lm, tracer):
    """One route span per submitted stream, one migrate span per
    migration, and the terminal request spans agree with both — the
    cross-engine request timeline reconciles exactly."""
    prompts = _prompts(2, seed=11)
    with silenced("serve"):
        router, fleets, (pf_eng, dc_eng) = build_disagg(
            lm, SLOTS, MAX_SEQ, 8, pf_pace_s=0.0)
    try:
        with silenced("serve"):
            for p in prompts:
                router.submit("lm", p, max_new_tokens=4).result(
                    timeout=120)
        rstats = router.stats()
    finally:
        with silenced("serve"):
            _stop(router, fleets)
    spans = tracer.snapshot()["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert len(by_name.get("route", [])) == len(prompts)
    # one migrate span per LEG: export on the source engine, import on
    # the destination — two per migration
    legs = {}
    for s in by_name.get("migrate", []):
        ph = s["args"]["phase"]
        legs[ph] = legs.get(ph, 0) + 1
    assert legs == {"export": len(prompts), "import": len(prompts)}
    assert rstats["migrations"] == len(prompts)
    assert tracer.terminal_phase_counts() == {"completed": len(prompts)}
    for s in by_name["route"]:
        assert s["args"]["host"] == "pf0"
        assert s["args"]["role"] == "prefill"
    # the registry families the router feeds
    assert router._c_migrations.labels(
        eng=router._eng, status="ok").value == len(prompts)
    assert router._c_bytes.value == rstats["migrated_bytes"] > 0


# ----------------------------------------------------------------------
# per-tenant autoscaling: the deterministic fake-clock cycle
# ----------------------------------------------------------------------
class TestAutoscaler:
    def test_grow_cap_decay_on_fake_clock(self):
        sc = TenantAutoscaler(window_s=4.0, every_s=1.0,
                              high_depth=4.0, low_depth=0.5,
                              grow=2.0, max_scale=4.0)
        # sustained load: weight doubles per decision...
        assert sc.observe("a", 8.0, 1.0, 0.0) == 2.0
        # ...but decisions are paced at every_s
        assert sc.observe("a", 8.0, 2.0, 0.5) is None
        assert sc.observe("a", 8.0, 2.0, 1.5) == 4.0
        # capped at base x max_scale — no change, so no decision
        assert sc.observe("a", 8.0, 4.0, 3.0) is None
        # burst over: the loaded samples age out of the window and the
        # borrowed share decays at the grant rate, never below base
        assert sc.observe("a", 0.0, 4.0, 8.0) == 2.0
        assert sc.observe("a", 0.0, 2.0, 9.5) == 1.0
        assert sc.observe("a", 0.0, 1.0, 11.0) is None
        sc.forget("a")
        assert sc.observe("a", 0.0, 1.0, 12.0) is None

    def test_operator_weight_scales_around_its_base(self):
        sc = TenantAutoscaler(every_s=1.0, grow=2.0, max_scale=2.0)
        # an operator-set 3.0 share scales around 3.0, not the default
        assert sc.observe("b", 9.0, 3.0, 0.0) == 6.0
        assert sc.observe("b", 9.0, 6.0, 2.0) is None  # at 2x base

    def test_validation(self):
        with pytest.raises(ValueError):
            TenantAutoscaler(grow=1.0)
        with pytest.raises(ValueError):
            TenantAutoscaler(low_depth=4.0, high_depth=4.0)
        with pytest.raises(ValueError):
            TenantAutoscaler(window_s=0.0)

    def test_fleet_wiring(self):
        sc = TenantAutoscaler()
        assert FleetEngine(autoscaler=sc).autoscaler is sc


# ----------------------------------------------------------------------
# cross-tenant dispatch sharing: bit-parity vs separate dispatch
# ----------------------------------------------------------------------
def _twin_registry():
    def builder(cfg):
        from flexflow_tpu.models import build_transformer_lm
        return build_transformer_lm(cfg, num_layers=1, d_model=32,
                                    num_heads=2, d_ff=64, seq_len=32,
                                    vocab_size=50)[0]

    reg = ModelRegistry()
    for name in ("a", "b"):
        reg.register(name, builder, engine="generation", batch_size=2,
                     generation={"slots": 2, "max_new_tokens": 8,
                                 "stats_every": 0})
    return reg


def test_share_identical_bit_parity(lm):
    """Two tenants of one graph (same exec_digest) served in shared
    dispatcher turns emit EXACTLY the tokens separate turns emit —
    sharing is a latency optimization, never a numerics change."""
    prompt = [3, 1, 4, 1, 5]
    outs = {}
    for share in (False, True):
        with silenced("serve"), FleetEngine(_twin_registry(),
                                            share_identical=share) as fl:
            streams = [(n, fl.submit(n, prompt, max_new_tokens=8))
                       for n in ("a", "b") for _ in range(2)]
            outs[share] = [(n, _tokens(s)) for n, s in streams]
    assert outs[True] == outs[False]
    # identical weights: both tenants emit the same greedy tokens
    toks = {t for _, t in ((n, tuple(o)) for n, o in outs[True])}
    assert len(toks) == 1


# ----------------------------------------------------------------------
# FF132: the disagg-topology gate (lint --fleet)
# ----------------------------------------------------------------------
def _lm_builder(cfg):
    from flexflow_tpu.models import build_transformer_lm
    return build_transformer_lm(cfg, num_layers=1, d_model=32,
                                num_heads=2, d_ff=64, seq_len=32,
                                vocab_size=50)[0]


def _role_registry(decode_gen=None, prefill_gen=None,
                   with_decode=True):
    reg = ModelRegistry()
    reg.register("pf", _lm_builder, engine="generation", batch_size=2,
                 role="prefill",
                 generation=dict({"slots": 2, "max_seq": 32,
                                  "stats_every": 0},
                                 **(prefill_gen or {})))
    if with_decode:
        reg.register("dc", _lm_builder, engine="generation",
                     batch_size=2, role="decode",
                     generation=dict({"slots": 2, "max_seq": 32,
                                      "stats_every": 0},
                                     **(decode_gen or {})))
    return reg


class TestFF132Gate:
    def test_prefill_without_decode_target(self):
        report, _ = fleet_gate_report(_role_registry(with_decode=False),
                                      hbm_gb=16.0)
        assert report.codes().count("FF132") == 1

    def test_undersized_decode_pool(self):
        report, rows = fleet_gate_report(
            _role_registry(decode_gen={"num_pages": 1}), hbm_gb=16.0)
        assert report.codes().count("FF132") == 1
        dc = next(r for r in rows if r["name"] == "dc")
        assert dc["kv_num_pages"] < dc["kv_slots"] * \
            dc["kv_pages_per_slot"]

    def test_page_size_disagreement(self):
        report, _ = fleet_gate_report(
            _role_registry(prefill_gen={"page_size": 8},
                           decode_gen={"page_size": 16}), hbm_gb=16.0)
        assert report.codes().count("FF132") == 1

    def test_well_formed_topology_passes(self):
        report, rows = fleet_gate_report(_role_registry(), hbm_gb=16.0)
        assert "FF132" not in report.codes()
        # prefill rows carry the migration staging chain as headroom
        pf = next(r for r in rows if r["name"] == "pf")
        assert pf["staging_bytes"] > 0
        assert pf["ff108_bytes"] > pf["resident_bytes"]


# ----------------------------------------------------------------------
# pages: the fixed-shape export/import round trip migration rides on
# ----------------------------------------------------------------------
@pytest.mark.parametrize("heads", [3, 2 * 64])
def test_export_import_pages_padded_roundtrip(heads):
    """Page-major is all migration asks of a leaf: a narrow minor dim
    and the pool's lane-dense folded one (heads x head_dim = 128) ride
    the same gather and scatter."""
    import jax.numpy as jnp

    num_pages, psize = 6, 4
    src = {"attn0": {
        "k": jnp.arange(num_pages * psize * heads,
                        dtype=jnp.float32).reshape(num_pages, psize,
                                                   heads)}}
    payload = export_pages(src, [2, 0], num_pages, pad_to=4)
    # padded to the pool's fixed row count: one XLA program per
    # geometry, never one per chain length
    assert payload["attn0"]["k"].shape == (4, psize, heads)
    src_np = np.asarray(src["attn0"]["k"])
    np.testing.assert_array_equal(payload["attn0"]["k"][:2],
                                  src_np[[2, 0]])
    # pad rows repeat the LAST real page — idempotent on import
    np.testing.assert_array_equal(payload["attn0"]["k"][2:],
                                  np.stack([src_np[0], src_np[0]]))
    dst = {"attn0": {"k": jnp.zeros((num_pages, psize, heads),
                                    jnp.float32)}}
    out = np.asarray(import_pages(dst, payload, [1, 3])["attn0"]["k"])
    np.testing.assert_array_equal(out[[1, 3]], src_np[[2, 0]])
    np.testing.assert_array_equal(out[[0, 2, 4, 5]],
                                  np.zeros((4, psize, heads)))


def test_export_pages_rejects_non_page_major():
    import jax.numpy as jnp

    bad = {"lstm0": {"state": jnp.zeros((3, 8), jnp.float32)}}
    with pytest.raises(ValueError, match="page-major"):
        export_pages(bad, [0], num_pages=6)
