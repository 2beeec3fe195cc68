"""Test harness: run everything on a virtual 8-device CPU mesh so distributed
behavior is exercised without TPU hardware (SURVEY §4: the TPU-side answer to
the reference's lack of cluster-free distributed testing).

The suite is CPU-only by construction: the platform is forced here (the
same effect as ``JAX_PLATFORMS=cpu``), so running it on a machine that
holds a chip never claims the chip.
"""

import os
import sys

# `pytest tests/...` puts tests/ itself on sys.path, not the repo root —
# make `tests.subproc` importable from every entry point
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "float32")

# Share one compilation cache across the in-process suite and the
# subprocess tests (tests/subproc.py) — the subprocess example corpus is
# compile-dominated and within-session reuse cuts the suite severalfold
# on slow judging machines.  The one rule of flexflow_tpu/compile_cache.py
# applies: a JAX_COMPILATION_CACHE_DIR given from outside is used as it
# is (never set in code, never cleared); otherwise the suite owns
# ``.jax_cache``, which is SESSION-SCOPED:
# cleared at session start (FF_TEST_KEEP_CACHE=1 opts out), because
# CROSS-session reuse of multi-device CPU executables is unsafe — a
# TP-partitioned program deserialized from a stale entry after a
# single-device run in the same process deadlocks its cross-module
# all-gather rendezvous and XLA hard-aborts the suite after 40 s
# ("Exiting to ensure a consistent program state"; reproduced
# deterministically with tests/test_nmt.py::test_nmt_tp_parity
# write-then-read cycles).  Within one session every reader shares the
# writer's process constellation, which is the configuration that works.
import shutil  # noqa: E402

from tests.subproc import CACHE_DIR, CACHE_DIR_IS_OURS  # noqa: E402


def _place_test_cache(owned: bool, keep: bool, cache_dir: str) -> None:
    """``owned`` False: the directory came from the environment — jax
    already reads it, so nothing is set and nothing is removed."""
    if not owned:
        return
    if not keep:
        shutil.rmtree(cache_dir, ignore_errors=True)
    # jax does not reliably mkdir on a cache WRITE, so a missing dir
    # turns every entry write into a UserWarning
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)


_place_test_cache(CACHE_DIR_IS_OURS,
                  bool(os.environ.get("FF_TEST_KEEP_CACHE")), CACHE_DIR)


def pytest_configure(config):
    # tier-1 runs the fast fault matrix (tests/test_faults.py: real OS
    # processes, no jax workers); anything needing >30 s — the
    # multi-process jax recovery runs — carries the `slow` marker instead
    config.addinivalue_line(
        "markers",
        "faults: fault-injection matrix (fast, supervisor-level; tier-1)")


# ---------------------------------------------------------------------------
# FF_LOCKWATCH=1 session gate (ISSUE 18, docs/concurrency.md): after the
# whole suite ran with instrumented locks, assert (a) the observed
# runtime acquisition-order graph is acyclic and (b) every runtime
# nested-acquisition edge between LIBRARY locks appears in the static
# FF151 graph — the static ⊇ runtime pin that makes fflock trustworthy.
# Edges touching test-local lock names are ignored (unit tests mint
# their own); lockwatch tests that fabricate cycles must reset().
# ---------------------------------------------------------------------------
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lockwatch_session_gate():
    yield
    from flexflow_tpu.obs import lockwatch
    if not lockwatch.enabled():
        return
    rep = lockwatch.report()
    if not rep["edges"]:
        return
    from flexflow_tpu.analysis import concurrency as cz
    an = cz.build()
    roster = set(an.locks)
    run_edges = {(e["src"], e["dst"]) for e in rep["edges"]
                 if e["src"] in roster and e["dst"] in roster}
    cycle = lockwatch.find_cycle(run_edges)
    assert cycle is None, (
        f"FF_LOCKWATCH: runtime lock-order cycle: {' -> '.join(cycle)}")
    extra = sorted(run_edges - set(an.edges))
    assert not extra, (
        "FF_LOCKWATCH: runtime nested-acquisition edges missing from "
        f"the static FF151 graph (run `flexflow-tpu lint "
        f"--concurrency` and close the gap): {extra}")
