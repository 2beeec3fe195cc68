"""Profile-calibrated cost model (ISSUE 7 tentpole).

The contracts pinned here:

* **Bit-identical uncalibrated path** — with no calibration table
  loaded (``estimator=None``, the default), simulator outputs and MCMC
  results equal the pre-calibration behavior exactly; the
  ``AnalyticEstimator`` itself reproduces ``op_compute_time`` bit for
  bit, so even an explicitly-analytic run cannot drift.
* **CalibrationTable round-trip** — save -> load -> identical digest;
  any content tamper flips the digest and ``--check`` fails.
* **Estimator semantics** — exact-key table hits rescale by the
  measured/analytic ratio; misses fall back tier by tier and finally to
  scale 1.0; the ridge estimator predicts finite positive times and
  degrades to analytic when underfed.
* **Calibrated simulation is one model everywhere** — SimSession
  evaluates bit-identical to one-shot ``simulate()`` under a calibrated
  estimator (the session consumes the same ``_op_plan`` rows).
* **CLI round-trip** — harvest -> table on disk -> ``calibrate
  --check`` validates schema/digest -> a search given ``--calibration``
  scores with an estimator that names its kind and the table's digest.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.search.calibration import (
    AnalyticEstimator, CalibrationTable, RidgeEstimator, TableEstimator,
    apply_step_correction, calibrated_spec, default_table,
    estimator_from_config, fit_step_correction, make_estimator,
    op_features, op_key, shape_bucket, table_key, validate_file,
    validate_table)
from flexflow_tpu.search.cost_model import (DEFAULT_SPEC, op_compute_time,
                                            spec_for_device)
from flexflow_tpu.search.mcmc import candidate_meshes, legal_configs, search
from flexflow_tpu.search.simulator import Simulator
from flexflow_tpu.tensor import Tensor

from tests.subproc import REPO, cached_env


def _transformer_layers():
    from flexflow_tpu.models.transformer import build_transformer
    cfg = FFConfig(batch_size=16, compute_dtype="float32")
    model, _, _ = build_transformer(cfg, num_layers=1, d_model=64,
                                    num_heads=2, d_ff=128, seq_len=16,
                                    vocab_size=100)
    return model.layers


def _linear_op(name="fc", shape=(128, 9216), out=4096):
    from flexflow_tpu.ops.linear import Linear
    return Linear(name, Tensor(shape, name=f"{name}_in"), out)


def _toy_table(measured_scale=3.0, n_entries=4):
    """Table whose every entry measures ``measured_scale``x analytic."""
    t = CalibrationTable(device_kind="cpu")
    for i in range(n_entries):
        op = _linear_op(f"l{i}", (8 * (2 ** i), 64), 32 * (2 ** i))
        dims = (1, 1)
        ana_f = op_compute_time(op, dims, DEFAULT_SPEC) * 1e3
        ana_b = op_compute_time(op, dims, DEFAULT_SPEC, backward=True) * 1e3
        t.add_op_sample(op_key(op, dims, "bfloat16"),
                        op_features(op, dims), ana_f,
                        ana_f * measured_scale, ana_b,
                        ana_b * measured_scale)
    return t


# ------------------------------------------------------------------
# keys / buckets

def test_shape_bucket_and_key():
    assert shape_bucket((24, 35, 100)) == "32x64x128"
    assert shape_bucket((1, 128)) == "1x128"
    assert table_key("conv2d", (128, 64, 112, 112), "bfloat16", 4) == \
        "conv2d|128x64x128x128|bfloat16|p4"
    op = _linear_op()
    assert op_key(op, (2, 1), "float32").endswith("|float32|p2")


def test_op_features_fields():
    op = _linear_op()
    f = op_features(op, (2, 1))
    assert f["nparts"] == 2.0 and f["fan_in"] == 1.0
    assert f["flops"] > 0 and f["out_volume"] == 128 * 4096


# ------------------------------------------------------------------
# table round-trip + validation

def test_table_roundtrip_digest_stable(tmp_path):
    t = _toy_table()
    t.add_dispatch_sample("train|toy|k1|b16", 12.5, n=2,
                          steps_per_dispatch=1)
    path = str(tmp_path / "t.json")
    d1 = t.save(path)
    t2 = CalibrationTable.load(path)
    assert t2.digest == d1 == t.digest
    assert t2.ops.keys() == t.ops.keys()
    assert t2.dispatch["train|toy|k1|b16"]["measured_ms"] == 12.5
    assert validate_file(path) == []


def test_table_tamper_fails_check(tmp_path):
    t = _toy_table()
    path = str(tmp_path / "t.json")
    t.save(path)
    data = json.load(open(path))
    key = next(iter(data["ops"]))
    data["ops"][key]["fwd"]["measured_ms"] *= 2
    with open(path, "w") as f:
        json.dump(data, f)
    errs = validate_file(path)
    assert errs and any("digest" in e for e in errs)


def test_validate_rejects_malformed():
    assert validate_table([]) == ["top level: want an object"]
    errs = validate_table({"kind": "calibration_table", "version": 1,
                           "device_kind": "cpu",
                           "ops": {"badkey": {"fwd": {"analytic_ms": -1,
                                                      "measured_ms": 1,
                                                      "n": 1},
                                              "features": {}}},
                           "digest": "sha256:0"})
    assert any("badkey" in e for e in errs)
    assert any("analytic_ms" in e for e in errs)
    assert validate_file(os.devnull)  # empty/unparseable -> errors


def test_seed_table_loads_and_validates():
    t = default_table()
    assert t.device_kind == "TPU v5 lite"
    assert len(t.ops) >= 13  # the 13 round-5 measured shapes
    # the conv7x7_s2 anchor the backward_overhead law cites
    key = "conv2d|128x64x128x128|bfloat16|p1"
    assert key in t.ops
    rec = t.ops[key]
    # measured bwd / analytic bwd ~= 3.4x (the fossil the comments cite)
    ratio = rec["bwd"]["measured_ms"] / rec["bwd"]["analytic_ms"]
    assert 3.0 < ratio < 3.8, ratio


# ------------------------------------------------------------------
# estimators

def test_analytic_estimator_bit_identical():
    op = _linear_op()
    est = AnalyticEstimator()
    for dims in ((1, 1), (4, 1), (2, 2)):
        for bwd in (False, True):
            assert est.op_time(op, dims, DEFAULT_SPEC, 2, bwd) == \
                op_compute_time(op, dims, DEFAULT_SPEC, 2, bwd)


def test_table_estimator_exact_hit_scales():
    t = _toy_table(measured_scale=3.0)
    est = TableEstimator(t)
    op = _linear_op("l0", (8, 64), 32)
    base = op_compute_time(op, (1, 1), DEFAULT_SPEC)
    got = est.op_time(op, (1, 1), DEFAULT_SPEC)
    assert got == pytest.approx(3.0 * base, rel=1e-9)


def test_table_estimator_fallback_tiers():
    t = _toy_table(measured_scale=2.0)
    est = TableEstimator(t)
    # same op type + dtype, unseen bucket/degree -> nearest-volume hit
    op = _linear_op("other", (16, 100), 50)
    base = op_compute_time(op, (4, 1), DEFAULT_SPEC)
    assert est.op_time(op, (4, 1), DEFAULT_SPEC) == \
        pytest.approx(2.0 * base, rel=1e-9)
    # unseen op type -> scale 1.0 (pure analytic)
    from flexflow_tpu.ops.tensor_ops import Reshape
    rs = Reshape("rs", Tensor((4, 8), name="x"), (8, 4))
    assert est.op_time(rs, (1, 1), DEFAULT_SPEC) == \
        op_compute_time(rs, (1, 1), DEFAULT_SPEC)


def test_ridge_estimator_fit_and_fallback():
    est = RidgeEstimator(_toy_table(measured_scale=3.0, n_entries=6))
    op = _linear_op("q", (32, 64), 64)
    tt = est.op_time(op, (1, 1), DEFAULT_SPEC)
    assert math.isfinite(tt) and tt > 0
    # an underfed table (< MIN_SAMPLES) degrades to analytic exactly
    lean = RidgeEstimator(_toy_table(n_entries=1))
    assert lean.op_time(op, (1, 1), DEFAULT_SPEC) == \
        op_compute_time(op, (1, 1), DEFAULT_SPEC)


def test_make_estimator_and_config_resolution(tmp_path):
    t = _toy_table()
    path = str(tmp_path / "t.json")
    t.save(path)
    assert make_estimator("analytic").name == "analytic"
    assert make_estimator("table", t).name == "table"
    assert make_estimator("ridge", t).name == "ridge"
    with pytest.raises(ValueError):
        make_estimator("table", None)
    with pytest.raises(ValueError):
        make_estimator("nope", t)
    # uncalibrated default: (None, None) — the bit-identical contract
    assert estimator_from_config(FFConfig()) == (None, None)
    cfg = FFConfig(calibration_file=path)  # auto -> table
    est, table = estimator_from_config(cfg)
    assert est.name == "table" and table.digest == t.digest
    cfg = FFConfig(calibration_file=path, cost_estimator="ridge")
    assert estimator_from_config(cfg)[0].name == "ridge"
    # analytic + file: no estimator, but the table (digest) is returned
    cfg = FFConfig(calibration_file=path, cost_estimator="analytic")
    est, table = estimator_from_config(cfg)
    assert est is None and table is not None


def test_fit_step_correction_power_law():
    # exact power law measured = e^0.5 * sim^0.8 -> recovered exactly
    pairs = [(x, math.exp(0.5) * x ** 0.8) for x in (0.5, 4.0, 900.0)]
    sc = fit_step_correction(pairs)
    assert sc["n"] == 3
    assert sc["alpha"] == pytest.approx(0.5, abs=1e-5)
    assert sc["beta"] == pytest.approx(0.8, abs=1e-5)
    t = CalibrationTable()
    t.step_correction = sc
    assert apply_step_correction(t, 4.0) == \
        pytest.approx(math.exp(0.5) * 4.0 ** 0.8, rel=1e-5)
    # identity without a correction / on non-finite inputs
    assert apply_step_correction(None, 3.0) == 3.0
    assert apply_step_correction(CalibrationTable(), 3.0) == 3.0
    assert math.isinf(apply_step_correction(t, float("inf")))
    # underfed or degenerate pairs refuse to fit
    assert fit_step_correction([(1.0, 2.0)]) is None
    assert fit_step_correction([(1.0, 2.0), (1.0, 3.0)]) is None
    assert fit_step_correction([(1.0, 4.0), (2.0, 1.0), (0, 0)]) is None


def test_step_correction_roundtrip_and_schema(tmp_path):
    t = _toy_table()
    t.step_correction = {"alpha": 1.1, "beta": 0.7, "n": 3}
    path = str(tmp_path / "t.json")
    t.save(path)
    t2 = CalibrationTable.load(path)
    assert t2.step_correction == t.step_correction
    assert validate_file(path) == []
    bad = t.to_json()
    bad["step_correction"] = {"alpha": 1.0, "beta": float("nan"), "n": 3}
    assert any("step_correction.beta" in e for e in validate_table(bad))
    bad["step_correction"] = {"alpha": 1.0, "beta": 0.7, "n": 1}
    assert any("step_correction.n" in e for e in validate_table(bad))


def test_calibrated_spec_overrides():
    t = _toy_table()
    assert calibrated_spec(None) == spec_for_device()
    assert calibrated_spec(t) == spec_for_device()  # no overrides
    t.spec = {"ici_bw": 5e10, "hbm_bw": 1e12}
    s = calibrated_spec(t)
    assert s.ici_bw == 5e10 and s.hbm_bw == 1e12
    assert s.mxu_flops == spec_for_device().mxu_flops  # untouched


# ------------------------------------------------------------------
# simulator / session / search integration

def test_uncalibrated_simulator_unchanged():
    layers = _transformer_layers()
    mesh = candidate_meshes(8)[0]
    strat = {op.name: legal_configs(op, mesh)[0] for op in layers}
    t0 = Simulator(num_devices=8).simulate(layers, strat)
    t1 = Simulator(num_devices=8, estimator=None).simulate(layers, strat)
    assert t0 == t1
    # fixed-seed search results equal with and without the None kwarg
    r1 = search(layers, 8, budget=40, seed=3)
    r2 = search(layers, 8, budget=40, seed=3, estimator=None)
    assert r1[2] == r2[2] and r1[0] == r2[0] and r1[1] == r2[1]


def test_calibrated_session_matches_one_shot():
    """The calibrated objective is ONE model: SimSession (native or
    python) returns exactly what one-shot simulate() does under a
    TableEstimator, across a seeded proposal walk."""
    layers = _transformer_layers()
    est = TableEstimator(default_table())
    sim = Simulator(num_devices=8, estimator=est)
    meshes = candidate_meshes(8)[:3]
    rng = np.random.default_rng(7)
    with sim.session(layers) as sess:
        mesh = meshes[0]
        strat = {op.name: legal_configs(op, mesh)[0] for op in layers}
        for step in range(25):
            if step % 9 == 8:
                mesh = meshes[int(rng.integers(len(meshes)))]
                strat = {op.name: legal_configs(op, mesh)[-1]
                         for op in layers}
            else:
                op = layers[int(rng.integers(len(layers)))]
                cands = legal_configs(op, mesh)
                strat[op.name] = cands[int(rng.integers(len(cands)))]
            t_sess = sess.evaluate(strat, mesh_shape=mesh)
            t_one = sim.simulate(layers, strat, mesh_shape=mesh)
            assert t_sess == t_one or (np.isinf(t_sess)
                                       and np.isinf(t_one)), step


def test_calibration_changes_objective_and_search_runs():
    layers = _transformer_layers()
    mesh = candidate_meshes(8)[0]
    strat = {op.name: legal_configs(op, mesh)[0] for op in layers}
    est = TableEstimator(default_table())
    t_cal = Simulator(num_devices=8, estimator=est).simulate(layers, strat)
    t_ana = Simulator(num_devices=8).simulate(layers, strat)
    assert t_cal != t_ana  # the table actually moved the objective
    best, bmesh, bt = search(layers, 8, budget=30, seed=0, estimator=est)
    assert math.isfinite(bt) and isinstance(best, dict)


def test_search_shared_sim_estimator_contradiction_warns():
    layers = _transformer_layers()
    sim = Simulator(num_devices=4)  # analytic
    est = TableEstimator(default_table())
    with pytest.warns(UserWarning, match="estimator"):
        search(layers, 4, budget=5, seed=0, estimator=est, sim=sim)


# ------------------------------------------------------------------
# CLI round-trip (subprocess; tiny scope to stay tier-1-fast)

@pytest.mark.parametrize("estimator", ["table", "ridge"])
def test_cli_calibrate_roundtrip_and_a_search_reads_the_table(tmp_path,
                                                             estimator):
    table_path = str(tmp_path / "table.json")
    cli = [sys.executable, "-m", "flexflow_tpu.cli"]
    r = subprocess.run(
        cli + ["calibrate", "--models", "transformer", "--iters", "1",
               "--degrees", "1", "--no-dispatch", "--out", table_path],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    wrote = json.loads(r.stdout.strip().splitlines()[-1])
    assert wrote["op_entries"] > 0 and wrote["digest"].startswith("sha256:")
    # --check validates the table it just wrote
    r = subprocess.run(cli + ["calibrate", "--check", table_path],
                       capture_output=True, text=True, env=cached_env(),
                       cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    # a search reads it through the flags a user passes: the estimator
    # it scores with names the kind and the digest of THIS table
    cfg = FFConfig.parse_args(["--calibration", table_path,
                               "--cost-estimator", estimator])
    est, table = estimator_from_config(cfg)
    assert est.describe() == {"estimator": estimator,
                              "calibration_digest": wrote["digest"]}
    assert table.digest == wrote["digest"]
    _best, _mesh, t = search(_transformer_layers(), 4, budget=5, seed=0,
                             estimator=est)
    assert math.isfinite(t) and t > 0


def test_cli_calibrate_check_rejects_tamper(tmp_path):
    t = _toy_table()
    path = str(tmp_path / "t.json")
    t.save(path)
    data = json.load(open(path))
    data["device_kind"] = "edited"
    with open(path, "w") as f:
        json.dump(data, f)
    r = subprocess.run(
        [sys.executable, "-m", "flexflow_tpu.cli", "calibrate",
         "--check", path],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=300)
    assert r.returncode == 1
    assert "digest mismatch" in r.stdout


# ------------------------------------------------------------------
# lint --calibration (FF108 under a calibrated spec)

def test_lint_calibration_table_tightens_hbm(tmp_path):
    """A table carrying a tiny measured hbm_capacity must flip the FF108
    verdict exactly like --hbm-gb does — lint and search legality read
    the same calibrated spec."""
    from flexflow_tpu.config import ParallelConfig
    from flexflow_tpu.strategy.proto import save_strategy_file
    t = CalibrationTable(device_kind="cpu")
    t.spec = {"hbm_capacity": 1e6}
    t.xla_temp_factor = 3.0
    table_path = str(tmp_path / "tight.json")
    t.save(table_path)
    pb = str(tmp_path / "s.pb")
    save_strategy_file(pb, {"ffn_up_0": ParallelConfig(
        dims=(2, 1, 1), device_ids=(0, 1))})
    cli = [sys.executable, "-m", "flexflow_tpu.cli", "lint",
           "--model", "transformer", "--strategy", pb, "--no-resharding"]
    r = subprocess.run(cli + ["--calibration", table_path],
                       capture_output=True, text=True, env=cached_env(),
                       cwd=REPO, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FF108" in r.stdout and "3.0x" in r.stdout
    # without the table the same strategy lints clean
    r = subprocess.run(cli, capture_output=True, text=True,
                       env=cached_env(), cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


# ------------------------------------------------------------------
# harvest units (no subprocess, tiny ops)

def test_harvest_ops_records_entries():
    from flexflow_tpu.search.calibration import harvest_ops
    op = _linear_op("hv", (8, 16), 8)
    t = CalibrationTable(device_kind="cpu")
    n = harvest_ops(t, [op], compute_dtype="float32", iters=1, warmup=1)
    assert n == 1 and len(t.ops) == 1
    ((key, entry),) = t.ops.items()
    assert key == op_key(op, (1, 1), "float32")
    assert entry["fwd"]["measured_ms"] > 0
    assert entry["features"]["out_volume"] == 64


def test_harvest_serve_dispatch_from_snapshot():
    from flexflow_tpu.search.calibration import harvest_serve_dispatch
    t = CalibrationTable()
    snap = {"per_bucket": {
        "4": {"dispatches": 3, "rows": 10, "dispatch_p50_ms": 1.5,
              "dispatch_p95_ms": 2.0, "dispatch_p99_ms": 2.0},
        "8": {"dispatches": 1, "rows": 8, "dispatch_p50_ms": 2.5,
              "dispatch_p95_ms": 2.5, "dispatch_p99_ms": 2.5}}}
    assert harvest_serve_dispatch(t, "m", snap) == 2
    assert t.dispatch["serve|m|bucket4"]["measured_ms"] == 1.5
    assert t.dispatch["serve|m|bucket8"]["bucket"] == 8
