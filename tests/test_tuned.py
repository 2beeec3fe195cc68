"""Measured kernel-path defaults (flexflow_tpu/tuned.py) resolution order."""

import json

import flexflow_tpu.tuned as tuned


def _fresh(monkeypatch, tmp_path, table):
    path = tmp_path / "tuned_defaults.json"
    path.write_text(json.dumps(table))
    monkeypatch.setattr(tuned, "_TUNED_PATH", str(path))
    tuned._tuned_table.cache_clear()
    tuned._device_kind.cache_clear()


def test_env_wins_over_table(monkeypatch, tmp_path):
    _fresh(monkeypatch, tmp_path,
           {"fast_pool": {tuned._device_kind(): True}})
    monkeypatch.setenv("FF_FAST_POOL", "0")
    assert tuned.flag_enabled("FF_FAST_POOL", "fast_pool") is False


def test_table_entry_for_device_kind(monkeypatch, tmp_path):
    kind = tuned._device_kind()
    _fresh(monkeypatch, tmp_path, {"fast_pool": {kind: False}})
    monkeypatch.delenv("FF_FAST_POOL", raising=False)
    assert tuned.flag_enabled("FF_FAST_POOL", "fast_pool") is False
    # other device kinds in the table don't apply
    _fresh(monkeypatch, tmp_path, {"fast_pool": {kind + "-other": False}})
    assert tuned.flag_enabled("FF_FAST_POOL", "fast_pool") is True


def test_default_when_table_absent(monkeypatch, tmp_path):
    _fresh(monkeypatch, tmp_path, {})
    monkeypatch.delenv("FF_FAST_POOL", raising=False)
    assert tuned.flag_enabled("FF_FAST_POOL", "fast_pool") is True
    assert tuned.flag_enabled("FF_FAST_POOL", "fast_pool",
                              default=False) is False
