"""What stands between the program and the chip, checked without one
(ISSUE 21): chip_smoke.py refuses to run off a TPU, the compile cache is
placed by one rule, an unknown TPU kind is an error where a default would
steer the search by the wrong machine, Pallas interprets only on the CPU,
and a run asked for more chips than are visible does not train on fewer.
"""

import os
import subprocess
import sys
import types

import pytest

import jax

from tests.subproc import REPO, cached_env


def test_chip_smoke_without_tpu_exits_nonzero_and_names_platform():
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=cached_env())
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout.splitlines()[0]
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout  # no result line


def test_chip_smoke_has_no_try_and_spawns_nothing():
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    import ast
    tree = ast.parse(src)
    legs = [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
            and n.name.startswith("leg_")]
    assert len(legs) >= 4
    for leg in legs:
        assert not any(isinstance(n, ast.Try) for n in ast.walk(leg)), \
            leg.name
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names}
    assert not imported & {"subprocess", "multiprocessing"}


def test_compile_cache_rule(monkeypatch, tmp_path):
    from flexflow_tpu import compile_cache

    # a directory placed from outside (the env var, which jax reads into
    # its config at import, or the embedding harness): untouched by us
    current = jax.config.jax_compilation_cache_dir
    assert current  # conftest or the environment placed the suite's
    assert compile_cache.enable() == current
    assert jax.config.jax_compilation_cache_dir == current
    # nothing placed: the fixed <checkout>/.jax_cache_chip
    updates = []
    fake_jax = types.SimpleNamespace(config=types.SimpleNamespace(
        jax_compilation_cache_dir=None,
        update=lambda k, v: updates.append((k, v))))
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    assert compile_cache.enable() == os.path.join(REPO, ".jax_cache_chip")
    assert updates == [("jax_compilation_cache_dir",
                        os.path.join(REPO, ".jax_cache_chip"))]


def test_conftest_does_not_clear_a_given_cache_dir(tmp_path):
    from tests.conftest import _place_test_cache

    entry = tmp_path / "entry"
    entry.write_text("x")
    before = jax.config.jax_compilation_cache_dir
    _place_test_cache(False, False, str(tmp_path))  # given from outside
    assert entry.exists()
    assert jax.config.jax_compilation_cache_dir == before


def _fake_devices(platform, kind):
    return lambda *a, **k: [types.SimpleNamespace(platform=platform,
                                                  device_kind=kind)]


def test_spec_for_device_unknown_tpu_kind_raises(monkeypatch):
    from flexflow_tpu.search.cost_model import (DEFAULT_SPEC, V5E_SPEC,
                                                spec_for_device)

    assert spec_for_device() is DEFAULT_SPEC  # the CPU mesh keeps it
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    assert spec_for_device() is V5E_SPEC
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v9"))
    with pytest.raises(ValueError, match="TPU v9"):
        spec_for_device()


def test_pallas_interprets_only_on_cpu(monkeypatch):
    from flexflow_tpu.ops import pallas_norm

    assert pallas_norm._interpret() is True  # this suite runs on cpu
    for backend in ("tpu", "some_plugin"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert pallas_norm._interpret() is False


def test_more_chips_than_visible_is_an_error(tmp_path):
    """-ll:tpu 16 on the 8-device mesh: compile() raises instead of
    warning and training on 8 — but a strategy export (-s) asked for in
    the same run is written first."""
    import flexflow_tpu as ff
    from flexflow_tpu.config import ParallelConfig

    out = tmp_path / "s.pb"
    cfg = ff.FFConfig.parse_args(["-ll:tpu", "16", "-b", "16",
                                  "-s", str(out)])
    cfg.strategies = {"dense": ParallelConfig.data_parallel(16)}
    model = ff.FFModel(cfg)
    x = model.create_tensor((16, 8), name="x")
    model.dense(x, 4)
    with pytest.raises(ValueError, match="only 8 are visible"):
        model.compile(ff.SGDOptimizer(lr=0.1))
    assert out.exists()


def test_elastic_supervisor_never_initialises_a_backend(tmp_path):
    """One process per chip: the elastic supervisor spawns the workers
    that need the chips, so it must never claim one itself.  A fresh
    interpreter imports the CLI, runs a whole supervised attempt (two
    trivial workers) and ends with no jax backend initialised."""
    code = (
        "import sys\n"
        "import flexflow_tpu.cli\n"
        "from flexflow_tpu.parallel.elastic import run_elastic\n"
        "rep = run_elastic(lambda a, p, r: [sys.executable, '-c', 'pass'],\n"
        "                  num_processes=2, max_restarts=0,\n"
        "                  attempt_timeout_s=60)\n"
        "from jax._src import xla_bridge\n"
        "print('SUPERVISOR', rep.success,\n"
        "      xla_bridge.backends_are_initialized())\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=REPO, env=cached_env())
    assert p.returncode == 0, p.stderr[-2000:]
    assert "SUPERVISOR True False" in p.stdout, p.stdout
