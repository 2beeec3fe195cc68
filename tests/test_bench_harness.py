"""bench.py harness: the sweep records every model's outcome, and nothing
in it lets a failure pass for a success.

These tests exercise the sweep loop and the no-TPU / unknown-device exits
WITHOUT a backend: the per-model bench function is injected.  The chip
path itself is exercised by chip_smoke.py through the chip tool.
"""

import json

import bench  # repo root is on sys.path via tests/conftest.py


def _fake_bench(rows):
    def f(name, batch_size, iters):
        r = rows[name]
        if isinstance(r, Exception):
            raise r
        return r
    return f


def test_sweep_survives_per_model_failure(capsys):
    rows = {
        "inception_v3": {"metric": "inception_v3_train_samples_per_sec_per_chip",
                         "value": 2400.0, "mfu": 0.43, "ms_per_step": 53.0,
                         "vs_baseline": 1.5, "batch_size": 128},
        "alexnet": RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
        "dlrm": {"metric": "dlrm_train_samples_per_sec_per_chip",
                 "value": 9000.0, "hbm_bw_util": 0.41, "batch_size": 2048},
    }
    summary = bench.run_sweep(["inception_v3", "alexnet", "dlrm"],
                              _bench=_fake_bench(rows))
    assert summary["models_ok"] == 2 and summary["models_total"] == 3
    # headline fields come from inception even with a mid-sweep failure
    assert summary["value"] == 2400.0 and summary["mfu"] == 0.43
    assert "RESOURCE_EXHAUSTED" in summary["results"]["alexnet"]["error"]
    assert summary["results"]["dlrm"]["hbm_bw_util"] == 0.41
    # one parseable JSON line per completed model + the summary line
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 4


def test_sweep_time_budget_skips_not_fails():
    rows = {"inception_v3": {"metric": "m", "value": 1.0}}
    summary = bench.run_sweep(["inception_v3", "alexnet"], budget_s=-1.0,
                              _bench=_fake_bench(rows))
    assert summary["models_ok"] == 0
    assert "skipped" in summary["results"]["inception_v3"]
    assert "skipped" in summary["results"]["alexnet"]


def test_main_exits_nonzero_when_any_model_fails(monkeypatch, capsys):
    """One model survived, one raised: rows for both, exit code 1."""
    import pytest

    def fake(name, batch_size, iters):
        if name == "alexnet":
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return {"metric": "m", "value": 1.0}

    monkeypatch.setattr(bench, "_require_tpu", lambda: None)
    monkeypatch.setattr(bench, "bench_model", fake)
    with pytest.raises(SystemExit) as e:
        bench.main(["--models", "inception_v3,alexnet"])
    assert e.value.code == 1
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["models_ok"] == 1 and lines[-1]["models_total"] == 2
    bench.main(["--models", "inception_v3"])  # all ok: returns normally


def test_no_tpu_exits_nonzero_before_building(capsys):
    """On the CPU test mesh bench.py refuses to run: an error line, exit
    code 1, and no model was built (bench_model would take minutes)."""
    import pytest

    with pytest.raises(SystemExit) as e:
        bench.main(["--model", "alexnet"])
    assert e.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "bench_error" and "'cpu'" in line["error"]


def test_unknown_device_kind_raises():
    import pytest

    assert bench._peak(bench.PEAK_FLOPS, "TPU v5 lite") == 197e12
    for table in (bench.PEAK_FLOPS, bench.HBM_BW):
        with pytest.raises(ValueError, match="TPU v9"):
            bench._peak(table, "TPU v9")
