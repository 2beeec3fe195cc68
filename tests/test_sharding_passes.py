"""Static sharding propagation (ISSUE 9): the FF120 prediction equals
the runtime-recorded FF106 fallback set bit-for-bit, the liveness HBM
timeline upper-bounds the one-shot memory bound, the communication plan
and ``flexflow-tpu explain`` are device-free, and inference-only
sessions surface their fallbacks.

The cross-validation has two layers: a ~200-strategy seeded property
sweep that runs the TRACE-TIME placement functions (real
``MachineMesh`` + the runtime recorder) against the static pass (the
same functions on a device-free ``AbstractMesh``), and full end-to-end
compile/train/evaluate/predict/serve runs on the zoo models comparing
``model.runtime_fallback_sites`` with the static prediction."""

import json
import subprocess
import sys

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.analysis import (comm_plan_digest, communication_plan,
                                   drain_fallback_sites,
                                   drain_replicate_fallbacks,
                                   explain_report, predict_fallbacks,
                                   validate_explain_json,
                                   validate_report_json)
from flexflow_tpu.config import FFConfig, ParallelConfig
from flexflow_tpu.models.dlrm import build_dlrm
from flexflow_tpu.models.transformer import build_transformer
from flexflow_tpu.parallel.mesh import AbstractMesh, MachineMesh
from flexflow_tpu.search.simulator import Simulator
from tests.subproc import REPO, cached_env


def _small_transformer(batch=8):
    cfg = FFConfig(batch_size=batch, compute_dtype="float32")
    model, tokens, logits = build_transformer(
        cfg, num_layers=1, d_model=32, num_heads=2, d_ff=64, seq_len=8,
        vocab_size=128, num_classes=4)
    return model, logits


def _small_dlrm(batch=8):
    cfg = FFConfig(batch_size=batch, compute_dtype="float32")
    model, inputs, preds = build_dlrm(
        cfg, embedding_size=(64, 64), sparse_feature_size=8,
        mlp_bot=(4, 16, 8), mlp_top=(24, 16, 1))
    return model, preds


def _random_strategy(layers, rng) -> dict:
    """A seeded ARBITRARY strategy: legal and illegal degrees mixed, on
    a random subset of ops — exactly the inputs whose fallback behavior
    the static pass must predict."""
    degrees = (1, 2, 3, 4, 5, 8)
    out = {}
    for op in layers:
        if not op.outputs or rng.random() < 0.3:
            continue
        nd = op.outputs[0].num_dims
        dims = tuple(int(rng.choice(degrees)) for _ in range(nd))
        nparts = int(np.prod(dims))
        out[op.name] = ParallelConfig(dims=dims,
                                      device_ids=tuple(range(nparts)))
    return out


def _trace_time_sites(layers, strategies, mesh: MachineMesh):
    """The RUNTIME's fallback record for this (graph, strategy, mesh):
    run the exact trace-time placement calls (output_spec per output of
    every configured op, param_spec per parameter — what _run_ops and
    _placed_param do) against a real MachineMesh and drain the
    process-global recorder."""
    from flexflow_tpu.parallel.sharding import output_spec, param_spec

    drain_fallback_sites()  # isolate from prior traces
    seen = set()
    for op in layers:
        pc = strategies.get(op.name)
        if pc is not None and mesh.is_distributed:
            for t in op.outputs:
                output_spec(t, pc, mesh)
        for w in op.weights:
            if w.uid in seen or not mesh.is_distributed:
                continue
            seen.add(w.uid)
            param_spec(w, pc, mesh)
    sites, _dropped = drain_fallback_sites()
    return set(sites)


# ---------------------------------------------------------------------
# THE property sweep (acceptance): ~200 seeded random strategies on the
# transformer + DLRM zoo, static == trace-time bit-for-bit on a CPU
# {n:4} mesh, and the HBM timeline upper-bounds the one-shot bound
# ---------------------------------------------------------------------

@pytest.mark.parametrize("builder,n_strategies", [
    (_small_transformer, 100), (_small_dlrm, 100)])
def test_static_fallback_prediction_matches_trace_property(
        builder, n_strategies):
    model, _ = builder()
    mmesh = MachineMesh({"n": 4})
    amesh = AbstractMesh({"n": 4})
    sim = Simulator(num_devices=4, use_native=False)
    rng = np.random.default_rng(90)
    mismatches = []
    for i in range(n_strategies):
        strategies = _random_strategy(model.layers, rng)
        static = set(predict_fallbacks(model.layers, strategies, amesh))
        runtime = _trace_time_sites(model.layers, strategies, mmesh)
        if static != runtime:
            mismatches.append((i, static ^ runtime))
        # liveness timeline >= the one-shot scalar bound, remat or not
        for remat in (False, True):
            tl = sim.memory_timeline(model.layers, strategies,
                                     {"n": 4}, assume_remat=remat)
            scalar = sim.peak_memory_bytes(model.layers, strategies,
                                           {"n": 4}, assume_remat=remat)
            assert tl["peak_bytes"] >= scalar, (i, remat)
            assert tl["peak_bytes"] >= tl["state_bytes"]
    assert not mismatches, mismatches[:3]


def test_abstract_mesh_answers_match_machine_mesh():
    """AbstractMesh must give MachineMesh's exact axis decisions — the
    shared _MeshAxes math, pinned over every (size, degree) pair the
    8-device test harness can express."""
    for n in (1, 2, 3, 4, 6, 8):
        mm = MachineMesh({"n": n})
        am = AbstractMesh({"n": n})
        assert am.num_devices == mm.num_devices
        for deg in range(1, 9):
            assert am.axis_spec("n", deg) == mm.axis_spec("n", deg), \
                (n, deg)
        assert am.axis_size("n") == mm.axis_size("n")
        if n > 1:
            # n == 1: MachineMesh keeps a placeholder ("n0",) sub-axis
            # because a jax Mesh needs >= 1 axis; the placement math
            # (axis_spec, asserted above) is identical either way
            assert am.subaxes("n") == mm.subaxes("n")
    big = AbstractMesh({"n": 64, "c": 4}, num_devices=512)
    assert big.num_devices == 512
    assert big.axis_spec("n", 16) is not None  # divisor of 64
    assert big.axis_spec("n", 48) is None      # not expressible
    with pytest.raises(ValueError, match="needs"):
        AbstractMesh({"n": 64}, num_devices=8)
    # is_distributed keys on the MESH product, not the machine size: a
    # product-1 mesh constrains nothing at trace time regardless of how
    # many devices the machine has, and the static pass must mirror
    # that (no FF120 the runtime would never record)
    lone = AbstractMesh({"n": 1}, num_devices=8)
    assert lone.num_devices == 8 and not lone.is_distributed
    # a typo'd axis fails loudly in BOTH mesh views — a bogus axis must
    # never produce a confidently wrong static report (or an opaque
    # device-reshape error at trace time)
    with pytest.raises(ValueError, match="unknown mesh axis"):
        AbstractMesh({"dp": 8})
    with pytest.raises(ValueError, match="unknown mesh axis"):
        MachineMesh({"dp": 8})
    assert AbstractMesh({"data": 4}).axis_size("n") == 4  # aliases ok
    assert predict_fallbacks(
        _small_transformer()[0].layers,
        {"ln_attn_0": ParallelConfig(dims=(3, 1, 1),
                                     device_ids=(0, 1, 2))}, lone) == {}


# ---------------------------------------------------------------------
# end-to-end: the zoo models, compiled + executed — static == runtime
# ---------------------------------------------------------------------

def _fallback_strategy_transformer():
    # degree 3 divides neither batch 8 nor the n=4 axis -> output AND
    # param sites fall back at trace time
    return {"ln_attn_0": ParallelConfig(dims=(3, 1, 1),
                                        device_ids=(0, 1, 2)),
            "ffn_up_0": ParallelConfig(dims=(3, 1, 1),
                                       device_ids=(0, 1, 2))}


def test_train_runtime_sites_equal_static_prediction_exactly():
    model, logits = _small_transformer()
    bad = _fallback_strategy_transformer()
    model.config.strategies = dict(bad)
    mesh = MachineMesh({"n": 4})
    with pytest.warns(UserWarning):
        model.compile(ff.SGDOptimizer(lr=0.1),
                      "sparse_categorical_crossentropy", [],
                      final_tensor=logits, mesh=mesh)
    # the static prediction is already in the compile report as FF120
    ff120 = [d for d in model.verify_report if d.code == "FF120"]
    assert ff120, "compile(verify=) must carry the static prediction"
    model.init_layers(seed=0)
    drain_replicate_fallbacks()
    rng = np.random.default_rng(0)
    x = rng.integers(0, 128, (8, 8)).astype(np.int32)
    y = rng.integers(0, 4, (8, 1)).astype(np.int32)
    model.train_batch(x, y)
    static = set(predict_fallbacks(model.layers, bad,
                                   AbstractMesh({"n": 4})))
    assert static, "seeded strategy must produce fallbacks"
    # THE acceptance criterion: static == runtime, exactly
    assert model.runtime_fallback_sites == static
    # and the report carries matching FF106/FF120 pairs per site op
    ff106_ops = {d.op for d in model.verify_report if d.code == "FF106"}
    assert ff106_ops == {d.op for d in ff120}


def test_evaluate_only_session_surfaces_fallbacks():
    model, logits = _small_transformer()
    model.config.strategies = dict(_fallback_strategy_transformer())
    with pytest.warns(UserWarning):
        model.compile(ff.SGDOptimizer(lr=0.1),
                      "sparse_categorical_crossentropy", [],
                      final_tensor=logits, mesh=MachineMesh({"n": 4}))
    model.init_layers(seed=0)
    drain_replicate_fallbacks()
    rng = np.random.default_rng(1)
    x = rng.integers(0, 128, (8, 8)).astype(np.int32)
    y = rng.integers(0, 4, (8, 1)).astype(np.int32)
    model.evaluate(x, y)  # NO train step ever runs
    assert model.runtime_fallback_sites == set(predict_fallbacks(
        model.layers, model.config.strategies, AbstractMesh({"n": 4})))
    assert any(d.code == "FF106" for d in model.verify_report)


def test_predict_only_session_surfaces_fallbacks():
    model, logits = _small_transformer()
    model.config.strategies = dict(_fallback_strategy_transformer())
    with pytest.warns(UserWarning):
        model.compile(ff.SGDOptimizer(lr=0.1),
                      "sparse_categorical_crossentropy", [],
                      final_tensor=logits, mesh=MachineMesh({"n": 4}))
    model.init_layers(seed=0)
    drain_replicate_fallbacks()
    rng = np.random.default_rng(2)
    x = rng.integers(0, 128, (8, 8)).astype(np.int32)
    model.predict(x)  # inference only
    assert model.runtime_fallback_sites == set(predict_fallbacks(
        model.layers, model.config.strategies, AbstractMesh({"n": 4})))


def test_multi_model_process_drains_only_its_own_sites():
    """The recorder is process-global: model B's drain must not absorb
    (and mis-attribute) model A's fallback sites — the per-model filter
    leaves foreign sites recorded for their owner."""
    from flexflow_tpu.parallel.sharding import output_spec

    drain_fallback_sites()
    # model A records a fallback but never drains (no step executed)
    model_a, _ = _small_dlrm()
    mmesh = MachineMesh({"n": 4})
    pc = ParallelConfig(dims=(3, 1), device_ids=(0, 1, 2))
    a_op = next(op for op in model_a.layers if op.outputs
                and op.outputs[0].num_dims == 2)
    output_spec(a_op.outputs[0], pc, mmesh)

    # model B runs an inference-only session and drains
    model_b, logits = _small_transformer()
    model_b.config.strategies = dict(_fallback_strategy_transformer())
    with pytest.warns(UserWarning):
        model_b.compile(ff.SGDOptimizer(lr=0.1),
                        "sparse_categorical_crossentropy", [],
                        final_tensor=logits, mesh=mmesh)
    model_b.init_layers(seed=0)
    rng = np.random.default_rng(3)
    model_b.predict(rng.integers(0, 128, (8, 8)).astype(np.int32))
    static_b = set(predict_fallbacks(
        model_b.layers, model_b.config.strategies, AbstractMesh({"n": 4})))
    assert model_b.runtime_fallback_sites == static_b
    assert not any(s[0].startswith(a_op.name)
                   for s in model_b.runtime_fallback_sites)
    # model A's site is still recorded, awaiting ITS drain
    leftover, _ = drain_fallback_sites()
    assert any(s[0].startswith(a_op.name) for s in leftover)


def test_serving_engine_startup_surfaces_fallbacks():
    from flexflow_tpu.serving import ServingEngine
    model, logits = _small_transformer()
    model.config.strategies = dict(_fallback_strategy_transformer())
    with pytest.warns(UserWarning):
        model.compile(ff.SGDOptimizer(lr=0.1),
                      "sparse_categorical_crossentropy", [],
                      final_tensor=logits, mesh=MachineMesh({"n": 4}))
    model.init_layers(seed=0)
    drain_replicate_fallbacks()
    engine = ServingEngine(model, max_batch=8, max_wait_ms=1.0)
    try:
        # bucket warmup traced the forward: the serving-only process
        # has its FF106 sites before a single request was served
        assert model.runtime_fallback_sites == set(predict_fallbacks(
            model.layers, model.config.strategies,
            AbstractMesh({"n": 4})))
    finally:
        engine.stop()


# ---------------------------------------------------------------------
# liveness HBM timeline + FF121
# ---------------------------------------------------------------------

def test_memory_timeline_shape_and_boundary_peak():
    model, _ = _small_transformer()
    strategies = {"ffn_up_0": ParallelConfig(
        dims=(2, 1, 1), device_ids=(0, 1))}
    sim = Simulator(num_devices=2, use_native=False)
    tl = sim.memory_timeline(model.layers, strategies, {"n": 2},
                             assume_remat=False)
    n = len(model.layers)
    assert len(tl["events"]) == 2 * n  # one fwd + one bwd per op
    phases = [e["phase"] for e in tl["events"]]
    assert phases == ["fwd"] * n + ["bwd"] * n
    # forward events carry no transient; backward events do
    assert all(e["transient_bytes"] == 0.0
               for e in tl["events"][:n])
    # the peak sits at the fwd/bwd boundary region and upper-bounds the
    # one-shot sum
    scalar = sim.peak_memory_bytes(model.layers, strategies, {"n": 2},
                                   assume_remat=False)
    assert tl["peak_bytes"] >= scalar
    assert tl["peak_event"]["phase"] == "bwd"
    assert tl["peak_owners"], "peak owners must be named"


def test_ff121_names_the_offending_interval():
    import dataclasses

    from flexflow_tpu.analysis import verify
    from flexflow_tpu.search.cost_model import V5P_SPEC
    model, _ = _small_transformer()
    tiny = dataclasses.replace(V5P_SPEC, hbm_capacity=1e4)
    report = verify(model.layers,
                    {"ffn_up_0": ParallelConfig(dims=(1, 1, 1))},
                    mesh_shape={"n": 1}, num_devices=1, spec=tiny,
                    check_resharding=False)
    codes = report.codes()
    assert "FF108" in codes  # the scalar gate still fires (ERROR)
    ff121 = [d for d in report if d.code == "FF121"]
    assert ff121, "the liveness bound must fire too"
    assert ff121[0].op, "FF121 anchors to the peak-owning op"
    assert "peak owners" in ff121[0].message
    # under the real budget neither fires
    report = verify(model.layers,
                    {"ffn_up_0": ParallelConfig(dims=(1, 1, 1))},
                    mesh_shape={"n": 1}, num_devices=1,
                    check_resharding=False)
    assert "FF121" not in report.codes()
    assert "FF108" not in report.codes()


# ---------------------------------------------------------------------
# communication plan + digest
# ---------------------------------------------------------------------

def test_comm_plan_edges_and_allreduce():
    model, _ = _small_transformer()
    # DP producer feeding a TP consumer: a real seam
    strategies = {
        "ffn_up_0": ParallelConfig(dims=(4, 1, 1),
                                   device_ids=tuple(range(4))),
        "ffn_down_0": ParallelConfig(dims=(1, 1, 4),
                                     device_ids=tuple(range(4))),
    }
    mesh = AbstractMesh({"n": 4, "c": 4})
    plan = communication_plan(model.layers, strategies, mesh)
    seam = [e for e in plan["edges"]
            if e["src"] == "ffn_up_0" and e["dst"] == "ffn_down_0"]
    assert seam and seam[0]["kind"] == "reshard"
    assert seam[0]["bytes_per_step"] > 0
    assert plan["totals"]["edge_bytes_per_step"] == sum(
        e["bytes_per_step"] for e in plan["edges"])
    # the DP split op's weights allreduce across its 4 replicas
    ar = [w for w in plan["weight_sync"] if w["op"] == "ffn_up_0"]
    assert ar and all(w["replicas"] == 4 for w in ar)
    # digest is deterministic and content-sensitive
    assert comm_plan_digest(plan) == comm_plan_digest(
        communication_plan(model.layers, strategies, mesh))
    other = communication_plan(model.layers, {}, mesh)
    assert comm_plan_digest(other) != comm_plan_digest(plan)


def test_explain_report_device_free_and_schema_valid():
    model, _ = _small_transformer()
    rep = explain_report(
        "transformer", model.layers,
        {"ffn_up_0": ParallelConfig(dims=(2, 1, 1),
                                    device_ids=(0, 1))},
        mesh_shape={"n": 16, "c": 4}, num_devices=64)
    assert validate_explain_json(rep) == []
    assert rep["num_devices"] == 64
    assert rep["mesh"]["n"] == 16 and rep["mesh"]["c"] == 4
    # a corrupted digest fails the schema check
    rep["comm_plan_digest"] = "0" * 16
    assert any("digest" in p for p in validate_explain_json(rep))


def test_explain_notes_machine_smaller_than_mesh():
    """An explicit --devices smaller than the mesh product must be
    surfaced, not silently overridden (lint gates it as FF112)."""
    from flexflow_tpu.analysis import render_explain_text
    model, _ = _small_transformer()
    rep = explain_report("transformer", model.layers, {},
                         mesh_shape={"n": 64}, num_devices=8)
    assert validate_explain_json(rep) == []
    assert rep["num_devices"] == 64
    assert rep["notes"] and "FF112" in rep["notes"][0]
    assert "NOTE:" in render_explain_text(rep)
    # no --devices at all -> the documented mesh-product default, with
    # NO spurious machine-too-small note
    rep = explain_report("transformer", model.layers, {},
                         mesh_shape={"n": 64})
    assert rep["num_devices"] == 64 and rep["notes"] == []


def test_explain_cli_64_device_mesh_from_single_cpu_device():
    """Acceptance: `flexflow-tpu explain` runs device-free on a
    64-device mesh spec from a machine with ONE visible CPU device (no
    forced host platform device count)."""
    env = cached_env()
    env.pop("XLA_FLAGS", None)  # 1 CPU device only
    r = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu.cli", "explain",
         "--model", "transformer", "--mesh", "n=32,c=2",
         "--devices", "64", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert validate_explain_json(rep) == []
    assert rep["num_devices"] == 64
    assert rep["predicted_fallbacks"] == []


def test_lint_json_schema_validates_and_detects_corruption():
    model, _ = _small_transformer()
    from flexflow_tpu.analysis import verify
    report = verify(model.layers,
                    {"ffn_up_0": ParallelConfig(
                        dims=(3, 1, 1), device_ids=(0, 1, 2))},
                    mesh_shape={"n": 3}, num_devices=3,
                    check_resharding=False)
    payload = json.loads(report.render_json())
    assert validate_report_json(payload) == []
    payload["diagnostics"][0]["code"] = "FF999"
    assert any("FF999" in p for p in validate_report_json(payload))


def test_shipped_strategy_artifact_gate_runs_clean():
    import os
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_strategy_artifacts.py")],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "lint + explain clean" in r.stdout


def test_searched_strategies_predict_zero_fallbacks():
    """The unification corollary: anything the search proposes executes
    as written — the static pass predicts zero fallbacks for a searched
    strategy (the simulator never costs a split the executor
    replicates)."""
    from flexflow_tpu.search.mcmc import search
    model, _ = _small_transformer()
    best, best_mesh, _t = search(model.layers, num_devices=4, budget=30,
                                 seed=0)
    amesh = AbstractMesh(best_mesh)
    assert predict_fallbacks(model.layers, best, amesh) == {}
