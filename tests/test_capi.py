"""C API: build the embedded-runtime shared library + a pure-C host program
and run the full graph-build/compile/train/verbs/weights sequence
(reference python/flexflow_c.{h,cc} surface — SURVEY §2.9a)."""

import os
import shutil
import site
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPI = os.path.join(REPO, "capi")


def capi_env():
    """Env for the embedded-CPython binaries: cached_env
    (JAX_PLATFORMS=cpu keeps the test off a TPU another process may
    hold, + the shared compile cache) + a PYTHONPATH the embedded
    interpreter can import from."""
    from tests.subproc import cached_env
    env = cached_env()
    paths = [REPO] + site.getsitepackages()
    env["PYTHONPATH"] = ":".join(paths + [env.get("PYTHONPATH", "")])
    return env


@pytest.mark.skipif(shutil.which("g++") is None or
                    shutil.which("python3-config") is None,
                    reason="no native toolchain")
def test_capi_builds_and_trains():
    r = subprocess.run(["make", "-C", CAPI], capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    env = capi_env()
    out = subprocess.run([os.path.join(CAPI, "test_capi")], cwd=CAPI,
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    assert "C API OK" in out.stdout


@pytest.mark.slow
@pytest.mark.skipif(shutil.which("g++") is None or
                    shutil.which("python3-config") is None,
                    reason="no native toolchain")
def test_capi_alexnet_example():
    """The pure-C AlexNet app (reference examples/cpp/AlexNet harness
    analogue): build graph, train, print the fenced throughput line."""
    r = subprocess.run(["make", "-C", CAPI, "examples"], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    env = capi_env()
    out = subprocess.run(
        [os.path.join(CAPI, "examples", "alexnet"), "-b", "8", "-e", "1"],
        cwd=CAPI, capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    assert "THROUGHPUT" in out.stdout
