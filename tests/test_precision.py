"""Precision as a SOAP axis + int8 weight-quantized serving (ISSUE 14).

Pins, in order: the dtype-dependent cost model and its bit-identical
default path (session == one-shot == native under MIXED precision),
the FF108/FF121 per-op dtype-bytes accounting, the MCMC precision axis
(mixed beats all-f32 on the zoo transformer; fp32-pinned ops never go
bf16; OFF = unchanged walk), trace-time per-op dtype resolution at the
ONE common.py point (all-f32 overrides bit-identical to the f32
session), the FF140/FF141 verifier codes flipping in ``lint --json``,
FFConfig dtype validation, int8 weight quantization (bound-by-
construction quality, engine == predict parity, training-verb guards,
exec-digest keying) and the gate==engine byte-for-byte pin for a
quantized fleet tenant."""

import dataclasses
import json
import subprocess
import sys

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.config import FFConfig, ParallelConfig
from flexflow_tpu.models import build_transformer
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.search.simulator import Simulator
from flexflow_tpu.strategy.proto import save_strategy_file

from tests.subproc import REPO, cached_env

LINT = [sys.executable, "-m", "flexflow_tpu.cli", "lint"]


def _zoo_transformer(batch=8, **kw):
    cfg = FFConfig(batch_size=batch, compute_dtype="float32")
    kw.setdefault("num_layers", 2)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("d_ff", 128)
    kw.setdefault("seq_len", 16)
    kw.setdefault("vocab_size", 100)
    model, _, _ = build_transformer(cfg, **kw)
    return model


def _dp_strategy(model, ndev=4):
    from flexflow_tpu.search.decompose import data_parallel_strategies
    return data_parallel_strategies(model.layers, ndev)


# ---------------------------------------------------------------------
# config / strategy atom
# ---------------------------------------------------------------------
def test_parallel_config_precision_validation():
    ParallelConfig(precision="bf16")
    ParallelConfig(precision="f32")
    with pytest.raises(ValueError, match="precision"):
        ParallelConfig(precision="fp8")
    # with_dims carries the token along
    pc = ParallelConfig(dims=(2, 1), device_ids=(0, 1), precision="bf16")
    assert pc.with_dims((4, 1)).precision == "bf16"


def test_ffconfig_dtype_validation_names_the_field():
    with pytest.raises(ValueError, match="compute_dtype"):
        FFConfig(compute_dtype="floaty")
    with pytest.raises(ValueError, match="param_dtype"):
        FFConfig(param_dtype="int8")
    with pytest.raises(ValueError, match="serve_quantize"):
        FFConfig(serve_quantize="int4")
    # the CLI flag validates too (construction happens before parse)
    with pytest.raises(ValueError, match="compute_dtype"):
        FFConfig.parse_args(["--compute-dtype", "floaty"])


def test_precision_policy_tag():
    cfg = FFConfig(compute_dtype="bfloat16")
    assert cfg.precision_policy() == "bf16"
    cfg = FFConfig(compute_dtype="float32", serve_quantize="int8")
    cfg.strategies["a"] = ParallelConfig(precision="bf16")
    cfg.strategies["b"] = ParallelConfig(precision="f32")
    assert cfg.precision_policy() == "f32+mixed(1bf16/1f32)+int8w"


# ---------------------------------------------------------------------
# cost model + simulator
# ---------------------------------------------------------------------
def test_op_compute_time_charges_precision():
    from flexflow_tpu.search.cost_model import op_compute_time
    model = _zoo_transformer()
    linear = next(op for op in model.layers
                  if op.op_type.value == "linear")
    t_default = op_compute_time(linear, (1, 1, 1), dtype_bytes=4)
    t_blank = op_compute_time(linear, (1, 1, 1), dtype_bytes=4,
                              precision="")
    assert t_blank == t_default  # "" is the bit-identical default
    t_bf16 = op_compute_time(linear, (1, 1, 1), dtype_bytes=4,
                             precision="bf16")
    t_f32 = op_compute_time(linear, (1, 1, 1), dtype_bytes=4,
                            precision="f32")
    assert t_bf16 < t_default       # half the activation traffic
    assert t_f32 >= t_default       # explicit f32: half MXU rate


def test_session_dtype_equal_pin_is_a_costing_noop():
    """An explicit pin EQUAL to the session dtype traces to the same
    program as the "" default — the simulator must charge them
    identically (effective_precision), in time AND memory."""
    model = _zoo_transformer()
    strat = _dp_strategy(model)
    pinned = {n: dataclasses.replace(pc, precision="f32")
              for n, pc in strat.items()}
    sim = Simulator(num_devices=4, use_native=False, dtype_bytes=4,
                    compute_dtype="float32")
    assert sim.simulate(model.layers, pinned) == \
        sim.simulate(model.layers, strat)
    assert sim.peak_memory_bytes(model.layers, pinned) == \
        sim.peak_memory_bytes(model.layers, strat)
    # ...and a bf16 pin under a bf16 session likewise
    sim_b = Simulator(num_devices=4, use_native=False, dtype_bytes=2,
                      compute_dtype="bfloat16")
    pinned_b = {n: dataclasses.replace(pc, precision="bf16")
                for n, pc in strat.items()}
    assert sim_b.simulate(model.layers, pinned_b) == \
        sim_b.simulate(model.layers, strat)


def test_table_estimator_charges_dtype_once():
    """An exact dtype-keyed table hit must not ALSO take the analytic
    f32 rate penalty — the measured/analytic ratio already embodies the
    dtype's physics (review fix: double-charge on exact-tier hits)."""
    from flexflow_tpu.search.calibration import (CalibrationTable,
                                                 TableEstimator, op_key)
    from flexflow_tpu.search.cost_model import (DEFAULT_SPEC,
                                                op_compute_time)
    model = _zoo_transformer()
    linear = next(op for op in model.layers
                  if op.op_type.value == "linear")
    dims = (1, 1, 1)
    analytic_ms = op_compute_time(linear, dims, DEFAULT_SPEC, 4) * 1e3
    t = CalibrationTable(device_kind="test", compute_dtype="float32")
    # a measured sample equal to the analytic time -> ratio 1.0
    t.add_op_sample(op_key(linear, dims, "float32"), {"out_volume": 1.0},
                    analytic_ms, analytic_ms)
    est = TableEstimator(t)
    got = est.op_time(linear, dims, DEFAULT_SPEC, 4,
                      compute_dtype="float32", precision="f32")
    # ratio 1.0 x base WITHOUT the rate penalty == the plain analytic
    assert got == pytest.approx(analytic_ms * 1e-3, rel=1e-12)


def test_ridge_estimator_precision_has_cost_signal():
    """The trained ridge path must distinguish precision tokens (review
    fix: a dtype-free feature vector made every precision flip cost
    delta == 0, so Metropolis accepted arbitrary pins): pinned times
    differ from the unpinned prediction by the analytic dtype ratio,
    and "" stays bit-identical to the trained prediction."""
    from flexflow_tpu.search.calibration import (CalibrationTable,
                                                 RidgeEstimator,
                                                 op_features, op_key)
    from flexflow_tpu.search.cost_model import DEFAULT_SPEC
    model = _zoo_transformer()
    linears = [op for op in model.layers
               if op.op_type.value == "linear"]
    t = CalibrationTable(device_kind="test", compute_dtype="float32")
    for i, op in enumerate(linears[:4]):
        # distinct partition degrees -> distinct table keys (same-shape
        # linears would otherwise merge below ridge's MIN_SAMPLES)
        dims = (2 ** i,) + (1,) * (op.outputs[0].num_dims - 1)
        t.add_op_sample(op_key(op, dims, "float32"),
                        op_features(op, dims), 1.0 + i, 2.0 + i,
                        1.0 + i, 3.0 + i)
    est = RidgeEstimator(t)
    assert est._w_fwd is not None  # trained, not the analytic fallback
    op = linears[0]
    dims = (1,) * op.outputs[0].num_dims
    base = est.op_time(op, dims, DEFAULT_SPEC, 4,
                       compute_dtype="float32")
    bf16 = est.op_time(op, dims, DEFAULT_SPEC, 4,
                       compute_dtype="bfloat16", precision="bf16")
    f32 = est.op_time(op, dims, DEFAULT_SPEC, 4,
                      compute_dtype="float32", precision="f32")
    assert bf16 < base  # the bytes credit reaches the learned path
    # the explicit-f32 rate penalty shows on compute-bound ops; this
    # small linear is bandwidth-bound, so equal-bytes f32 stays >= base
    assert f32 >= base
    assert est.op_time(op, dims, DEFAULT_SPEC, 4,
                       compute_dtype="float32", precision="") == base


def test_generation_engine_rejects_quantize_config():
    from flexflow_tpu.models import build_transformer_lm
    from flexflow_tpu.serving.generation import GenerationEngine
    cfg = FFConfig(batch_size=2, compute_dtype="float32",
                   serve_quantize="int8")
    m = build_transformer_lm(cfg, num_layers=1, d_model=32, num_heads=2,
                             d_ff=64, seq_len=16, vocab_size=50)[0]
    m.compile(ff.SGDOptimizer(lr=0.01))
    m.init_layers(seed=0)
    with pytest.raises(ValueError, match="generation"):
        GenerationEngine(m, slots=2)


def test_tenant_spec_rejects_quantize_in_serve_dict():
    from flexflow_tpu.serving.fleet import ModelRegistry
    reg = ModelRegistry()
    with pytest.raises(ValueError, match="tenant level"):
        reg.register("a", lambda cfg: None,
                     serve={"quantize": "int8"})


def test_mixed_precision_session_oneshot_native_bit_identical():
    model = _zoo_transformer()
    strat = _dp_strategy(model)
    mixed = {n: dataclasses.replace(
        pc, precision=("bf16" if i % 3 == 0 else
                       "f32" if i % 3 == 1 else ""))
        for i, (n, pc) in enumerate(sorted(strat.items()))}

    def one(use_native):
        return Simulator(num_devices=4, use_native=use_native,
                         dtype_bytes=4, compute_dtype="float32")

    sim_py = one(False)
    t_py = sim_py.simulate(model.layers, mixed)
    sess = sim_py.session(model.layers)
    assert sess.evaluate(mixed) == t_py
    # flipping one op's precision re-plans only that op, and flipping
    # back restores the exact value
    name = sorted(mixed)[0]
    flipped = dict(mixed)
    flipped[name] = dataclasses.replace(mixed[name], precision="f32")
    t_flip = sess.evaluate(flipped)
    assert t_flip == sim_py.simulate(model.layers, flipped)
    assert sess.evaluate(mixed) == t_py
    sess.close()
    sim_nat = one(True)
    if sim_nat._native is not None:
        assert sim_nat.simulate(model.layers, mixed) == t_py
        s2 = sim_nat.session(model.layers)
        assert s2.evaluate(mixed) == t_py
        s2.close()


def test_peak_memory_charges_per_op_dtype_bytes():
    model = _zoo_transformer()
    strat = _dp_strategy(model)
    sim = Simulator(num_devices=4, use_native=False, dtype_bytes=4,
                    compute_dtype="float32")
    base = sim.peak_memory_bytes(model.layers, strat)
    all_bf16 = {n: dataclasses.replace(pc, precision="bf16")
                for n, pc in strat.items()}
    less = sim.peak_memory_bytes(model.layers, all_bf16)
    assert less < base  # bf16 activations cost 2 B/elem, not 4
    # the "" default is bit-identical to strategies predating the field
    explicit = {n: dataclasses.replace(pc, precision="")
                for n, pc in strat.items()}
    assert sim.peak_memory_bytes(model.layers, explicit) == base
    # the FF121 timeline sees the same per-op rule
    tl_base = sim.memory_timeline(model.layers, strat)
    tl_bf = sim.memory_timeline(model.layers, all_bf16)
    assert tl_bf["peak_bytes"] < tl_base["peak_bytes"]


# ---------------------------------------------------------------------
# MCMC precision axis
# ---------------------------------------------------------------------
def test_search_precision_axis_beats_all_f32_on_zoo_transformer():
    """The acceptance criterion: with the axis enabled the walk finds a
    mixed-precision strategy whose simulated step time beats the
    all-f32 baseline, while fp32-pinned op classes never go bf16."""
    from flexflow_tpu.analysis.legality import F32_PINNED_OPS
    from flexflow_tpu.search.mcmc import search
    model = _zoo_transformer(batch=16, d_model=128, seq_len=32)

    def run(precision_axis):
        sim = Simulator(num_devices=4, dtype_bytes=4,
                        compute_dtype="float32")
        return search(model.layers, 4, budget=300, seed=0, sim=sim,
                      precision_axis=precision_axis)

    best, _, t_mixed = run(True)
    base, _, t_f32 = run(False)
    assert t_mixed < t_f32, (t_mixed, t_f32)
    n_bf16 = sum(1 for pc in best.values() if pc.precision == "bf16")
    assert n_bf16 > 0
    byname = {op.name: op for op in model.layers}
    for n, pc in best.items():
        if pc.precision == "bf16":
            assert byname[n].op_type not in F32_PINNED_OPS, n
    # OFF leaves the space untouched: no tokens appear
    assert all(pc.precision == "" for pc in base.values())


def test_search_default_rng_stream_unchanged_without_axis():
    """precision_axis=False must reproduce the axis-free walk exactly:
    same seed, same budget, same result, token-free strategies."""
    from flexflow_tpu.search.mcmc import search
    model = _zoo_transformer()

    def run():
        sim = Simulator(num_devices=4, dtype_bytes=4,
                        compute_dtype="float32")
        return search(model.layers, 4, budget=120, seed=3, sim=sim,
                      precision_axis=False)

    s1, m1, t1 = run()
    s2, m2, t2 = run()
    assert t1 == t2 and m1 == m2
    assert {n: pc.dims for n, pc in s1.items()} == \
        {n: pc.dims for n, pc in s2.items()}


# ---------------------------------------------------------------------
# trace-time per-op dtype (the ONE resolution point)
# ---------------------------------------------------------------------
def _mlp(strategies=None, dtype="float32", quantize=""):
    cfg = FFConfig(batch_size=4, compute_dtype=dtype, seed=0,
                   serve_quantize=quantize)
    if strategies:
        cfg.strategies.update(strategies)
    m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
    t = m.create_tensor((4, 32), name="x")
    t = m.dense(t, 32, activation="relu", name="d1")
    t = m.dense(t, 3, name="d2")
    m.softmax(t, name="head")
    m.compile(ff.SGDOptimizer(lr=0.1),
              loss_type="sparse_categorical_crossentropy", verify="off")
    m.init_layers(seed=0)
    return m


def _x(n=4):
    return np.random.default_rng(0).standard_normal((n, 32)).astype(
        np.float32)


def test_trace_time_precision_resolution():
    x = _x()
    base = _mlp().predict(x)
    # explicit f32 overrides on an f32 session: bit-identical programs
    f32s = {n: ParallelConfig(dims=(1, 1), device_ids=(0,),
                              precision="f32") for n in ("d1", "d2")}
    np.testing.assert_array_equal(_mlp(f32s).predict(x), base)
    # a bf16 pin on one op changes the traced program's numerics
    bf = {"d1": ParallelConfig(dims=(1, 1), device_ids=(0,),
                               precision="bf16")}
    out = _mlp(bf).predict(x)
    assert not np.array_equal(out, base)
    np.testing.assert_allclose(out, base, atol=0.1)


def test_resolve_op_dtype_is_the_single_point():
    from flexflow_tpu.ops.common import resolve_op_dtype
    model = _mlp({"d1": ParallelConfig(dims=(1, 1), device_ids=(0,),
                                       precision="bf16")})
    ops = {op.name: op for op in model.layers}
    assert resolve_op_dtype(ops["d1"], "float32") == "bfloat16"
    assert resolve_op_dtype(ops["d2"], "float32") == "float32"
    assert resolve_op_dtype(ops["d2"], "bfloat16") == "bfloat16"


# ---------------------------------------------------------------------
# verifier codes FF140/FF141 (+ lint --json flip)
# ---------------------------------------------------------------------
def test_lint_json_flips_precision_codes(tmp_path):
    ok = str(tmp_path / "prec_ok.pb")
    bad = str(tmp_path / "prec_bad.pb")
    save_strategy_file(ok, {"ffn_up_0": ParallelConfig(
        dims=(2, 1, 1), device_ids=(0, 1), precision="bf16")})
    # transformer's softmax head is an fp32-pinned class
    save_strategy_file(bad, {"softmax": ParallelConfig(
        dims=(1, 1), device_ids=(0,), precision="bf16")})

    def lint(path):
        r = subprocess.run(
            LINT + ["--model", "transformer", "--strategy", path,
                    "--json", "--no-resharding"],
            capture_output=True, text=True, env=cached_env(), cwd=REPO,
            timeout=300)
        return r.returncode, [d["code"] for d in
                              json.loads(r.stdout)["diagnostics"]]

    rc_ok, codes_ok = lint(ok)
    assert rc_ok == 0, codes_ok
    assert "FF141" in codes_ok and "FF140" not in codes_ok
    rc_bad, codes_bad = lint(bad)
    assert rc_bad == 1
    assert "FF140" in codes_bad
    # a default-precision strategy raises NEITHER code
    plain = str(tmp_path / "plain.pb")
    save_strategy_file(plain, {"ffn_up_0": ParallelConfig(
        dims=(2, 1, 1), device_ids=(0, 1))})
    rc_p, codes_p = lint(plain)
    assert rc_p == 0
    assert "FF140" not in codes_p and "FF141" not in codes_p


def test_compile_verify_error_rejects_pinned_bf16():
    from flexflow_tpu.analysis import VerificationError
    cfg = FFConfig(batch_size=4, compute_dtype="float32", seed=0)
    cfg.strategies["head"] = ParallelConfig(dims=(1, 1), device_ids=(0,),
                                            precision="bf16")
    m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
    t = m.create_tensor((4, 32), name="x")
    t = m.dense(t, 3, name="d2")
    m.softmax(t, name="head")
    with pytest.raises(VerificationError) as ei:
        m.compile(ff.SGDOptimizer(lr=0.1),
                  loss_type="sparse_categorical_crossentropy",
                  verify="error")
    assert any(d.code == "FF140" for d in ei.value.report)


# ---------------------------------------------------------------------
# int8 weight quantization
# ---------------------------------------------------------------------
def test_quantize_array_bound_holds_by_construction():
    from flexflow_tpu.serving.quantize import INT8_QMAX, quantize_array
    rng = np.random.default_rng(0)
    for scale_mag in (1e-3, 1.0, 37.5):
        w = (rng.standard_normal((64, 48)) * scale_mag).astype(np.float32)
        q, scale, err, bound = quantize_array(w)
        assert q.dtype == np.int8 and np.max(np.abs(q)) <= INT8_QMAX
        assert err <= bound, (err, bound, scale_mag)
        # per-channel: each row's error bounded by ITS scale/2 (+ulp)
        deq = q.astype(np.float32) * scale[:, None]
        row_err = np.max(np.abs(w - deq), axis=1)
        assert np.all(row_err <= scale * 0.5 * (1 + 1e-5))
    # a zero row is exact
    q, scale, err, bound = quantize_array(np.zeros((4, 8), np.float32))
    assert err == 0.0 and np.all(q == 0)


def test_quantized_engine_matches_predict_and_guards_training():
    from flexflow_tpu.fflogger import silenced
    from flexflow_tpu.serving.engine import ServingEngine
    model = _mlp(quantize="int8")
    x = _x(12)
    digest_before = model.exec_digest()
    rep = model.quantize_weights("int8")
    assert rep["bound_ok"] and len(rep["weights"]) == 2
    assert rep["bytes_after"] < rep["bytes_before"] / 2
    # quantization keys the executable cache
    assert model.exec_digest() != digest_before
    # idempotent
    assert model.quantize_weights("int8") is rep
    q_pred = model.predict(x)
    with silenced("serve"), ServingEngine(model) as eng:
        assert eng.quantize == "int8"
        out = eng.submit(x).result(timeout=60)
    np.testing.assert_array_equal(out, q_pred)
    # quantized vs full-precision: bounded deviation, not equality
    base = _mlp().predict(x)
    assert not np.array_equal(q_pred, base)
    np.testing.assert_allclose(q_pred, base, atol=0.2)
    for verb in ("fit", "train_batch", "evaluate", "save_checkpoint"):
        with pytest.raises(RuntimeError, match="quantized"):
            if verb == "fit":
                model.fit(x, np.zeros((12, 1), np.int32), epochs=1)
            elif verb == "train_batch":
                model.train_batch(x, np.zeros((12, 1), np.int32))
            elif verb == "evaluate":
                model.evaluate(x, np.zeros((12, 1), np.int32))
            else:
                model.save_checkpoint("/tmp/should_not_write.npz")


def test_engine_warmup_rejects_violated_bound(monkeypatch):
    from flexflow_tpu.serving.engine import ServingEngine
    model = _mlp(quantize="int8")
    model.quantize_weights("int8")
    # tamper the report: the warmup check must trip
    model._quant_report = dict(model._quant_report, bound_ok=False,
                               max_abs_err=1.0, error_bound=0.1)
    with pytest.raises(RuntimeError, match="quality bound"):
        ServingEngine(model)


def test_quantized_fleet_tenant_gate_matches_engine_byte_for_byte():
    from flexflow_tpu.fflogger import silenced
    from flexflow_tpu.serving.fleet import (FleetEngine, ModelRegistry,
                                            model_residency)

    def builder(cfg):
        cfg.seed = 1
        m = ff.FFModel(cfg, mesh=MachineMesh({"n": 1}))
        x = m.create_tensor((cfg.batch_size, 12), name="x")
        t = m.dense(x, 24, activation="relu")
        t = m.dense(t, 6)
        return m

    reg = ModelRegistry()
    reg.register("q", builder, batch_size=8, quantize="int8",
                 serve={"max_wait_ms": 0.5, "stats_every": 0})
    reg.register("d", builder, batch_size=8,
                 serve={"max_wait_ms": 0.5, "stats_every": 0})
    predicted = {}
    for name in reg.names():
        model, strategies = reg.graph(name)
        row = model_residency(reg.spec(name), model.layers,
                              model.input_tensors, strategies)
        predicted[name] = row["resident_bytes"]
    # the int8 tenant predicts a smaller footprint than its f32 twin
    assert predicted["q"] < predicted["d"]
    with silenced("serve"), FleetEngine(reg) as fleet:
        for name in reg.names():
            real = fleet.stats(name)["resident_bytes"]
            assert real == predicted[name], (name, real, predicted[name])


def test_fleet_schema_rejects_bad_quantize():
    from flexflow_tpu.serving.fleet import validate_fleet_json
    probs = validate_fleet_json({"fleet": [
        {"name": "a", "model": "transformer", "quantize": "int4"},
        {"name": "g", "model": "transformer_lm", "engine": "generation",
         "quantize": "int8"}]})
    text = "\n".join(probs)
    assert "quantize" in text and "dense" in text
    assert validate_fleet_json({"fleet": [
        {"name": "a", "model": "transformer", "quantize": "int8"}]}) == []
