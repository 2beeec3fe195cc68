"""Tier-1 smoke tests for the repo static gate (ISSUE 3): the
``flexflow-tpu lint`` CLI detects every seeded defect class with its
exact FFxxx code and nonzero exit, ``scripts/static_checks.sh`` runs
clean on the repo, and ``scripts/repo_lint.py`` enforces its RLxxx
invariants on synthetic violations."""

import functools
import glob
import os
import re
import subprocess
import sys

import pytest

from tests.subproc import REPO, cached_env

LINT = [sys.executable, "-m", "flexflow_tpu.cli", "lint"]


def _write_bad_strategy(path):
    from flexflow_tpu.config import ParallelConfig
    from flexflow_tpu.strategy.proto import save_strategy_file

    # transformer defaults: batch 64, seq 128, d_model 512, rank-3 outs
    save_strategy_file(path, {
        # FF101: 3 does not divide batch 64
        "ffn_up_0": ParallelConfig(dims=(3, 1, 1), device_ids=(0, 1, 2)),
        # FF102 (ERROR): 4 degrees on a rank-3 output, real tail degree
        "ffn_down_0": ParallelConfig(dims=(1, 1, 1, 2),
                                     device_ids=(0,)),
        # FF103: 2 ids for 4 parts
        "ln_attn_0": ParallelConfig(dims=(2, 2, 1), device_ids=(0, 1)),
        # FF104: id 99 on a 12-device machine
        "attention_0": ParallelConfig(dims=(2, 1, 1),
                                      device_ids=(0, 99)),
        # FF105: degree 4 divides batch 64 but not the n=6 axis
        "ffn_down_1": ParallelConfig(dims=(4, 1, 1),
                                     device_ids=(0, 1, 2, 3)),
        # duplicate-name case is covered at the proto layer
        # (tests/test_strategy_proto_roundtrip.py): loads() rejects it
    })


def test_lint_cli_detects_seeded_defects_with_exact_codes(tmp_path):
    bad = str(tmp_path / "bad.pb")
    _write_bad_strategy(bad)
    r = subprocess.run(
        LINT + ["--model", "transformer", "--strategy", bad,
                "--mesh", "n=6,c=2", "--devices", "12",
                "--no-resharding"],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=300)
    assert r.returncode == 1, r.stderr  # ERROR diagnostics -> exit 1
    out = r.stdout
    for code in ("FF101", "FF102", "FF103", "FF104", "FF105"):
        assert code in out, f"{code} missing from:\n{out}"
    assert "ERROR" in out and "summary:" in out


def test_lint_cli_memory_budget_and_clean_exit(tmp_path):
    from flexflow_tpu.config import ParallelConfig
    from flexflow_tpu.strategy.proto import save_strategy_file

    ok = str(tmp_path / "ok.pb")
    save_strategy_file(ok, {"ffn_up_0": ParallelConfig(
        dims=(2, 1, 1), device_ids=(0, 1))})
    # FF108: the default transformer cannot fit a 0.001 GB chip
    r = subprocess.run(
        LINT + ["--model", "transformer", "--strategy", ok,
                "--hbm-gb", "0.001", "--no-resharding"],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "FF108" in r.stdout
    # same strategy, real budget: clean -> exit 0
    r = subprocess.run(
        LINT + ["--model", "transformer", "--strategy", ok,
                "--no-resharding"],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    # malformed file -> usage/load failure exit 2, offset in message
    broken = str(tmp_path / "broken.pb")
    with open(broken, "wb") as f:
        f.write(b"\x0a\x63trunc")
    r = subprocess.run(
        LINT + ["--model", "transformer", "--strategy", broken],
        capture_output=True, text=True, env=cached_env(), cwd=REPO,
        timeout=300)
    assert r.returncode == 2
    assert "byte" in r.stderr


def test_static_checks_script_passes_on_repo():
    r = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "static_checks.sh")],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "static checks: OK" in r.stdout


@pytest.mark.parametrize("rel,src,code", [
    ("flexflow_tpu/zz_bad_ckpt.py",
     "import numpy as np\n\ndef f(path, d):\n    np.savez(path, **d)\n",
     "RL001"),
    ("flexflow_tpu/strategy/zz_bad_warn.py",
     "import warnings\n\ndef f():\n    warnings.warn('x')\n",
     "RL002"),
    ("flexflow_tpu/parallel/sharding_zz.py",  # not the scoped file
     "import warnings\n\ndef f():\n    warnings.warn('x')\n",
     None),
    ("tests/zz_bad_rng.py",
     "import numpy as np\nx = np.random.randn(3)\n",
     "RL003"),
    ("tests/zz_ok_rng.py",
     "import numpy as np\nr = np.random.default_rng(0)\n"
     "x = r.standard_normal(3)\n",
     None),
    # RL004: a float() host sync inside an evaluate() batch loop fences
    # the async dispatch pipeline every batch (ISSUE 4)
    ("flexflow_tpu/zz_bad_sync.py",
     "class M:\n"
     "    def evaluate(self, x, y):\n"
     "        s = 0.0\n"
     "        for b in self.loader:\n"
     "            s += float(self.step(b))\n"
     "        return s\n",
     "RL004"),
    # the per-EPOCH loop is the sanctioned sync point, and fetching in
    # the loop's ITER expression (once per loop entry) is the idiom
    ("flexflow_tpu/zz_ok_sync.py",
     "import jax\n\n"
     "class M:\n"
     "    def fit(self, x, y):\n"
     "        for epoch in range(2):\n"
     "            sums = []\n"
     "            for batch in self.loader:\n"
     "                sums.append(self.step(batch))\n"
     "            for s in jax.device_get(sums):\n"
     "                self.pm.update(s)\n"
     "            v = float(self.val_loss)\n"
     "        return v\n",
     None),
    # outside fit/evaluate/predict the rule does not engage
    ("flexflow_tpu/zz_ok_other.py",
     "def gather(items):\n"
     "    out = []\n"
     "    for it in items:\n"
     "        out.append(float(it))\n"
     "    return out\n",
     None),
    # a while-loop TEST re-evaluates per iteration: syncs there are
    # per-step syncs too
    ("flexflow_tpu/zz_bad_while.py",
     "class M:\n"
     "    def fit(self, x, y):\n"
     "        while float(self.loss) > 0.1:\n"
     "            self.step()\n",
     "RL004"),
    # RL005: a host sync inside a per-REQUEST loop of the serving
    # dispatch path fences once per request (ISSUE 5)
    ("flexflow_tpu/serving/zz_bad_scatter.py",
     "class E:\n"
     "    def _dispatch_batch(self, reqs):\n"
     "        out = self.run(reqs)\n"
     "        for r in reqs:\n"
     "            r.set_result(float(out))\n",
     "RL005"),
    # the sanctioned shape: ONE device_get per packed batch in
    # straight-line code, host slices scattered in the loop
    ("flexflow_tpu/serving/zz_ok_scatter.py",
     "import jax\n\n"
     "class E:\n"
     "    def _dispatch_batch(self, reqs):\n"
     "        host = jax.device_get(self.run(reqs))\n"
     "        for r in reqs:\n"
     "            r.set_result(host[r.i])\n",
     None),
    # the `while` serve loop is the per-batch granularity (the RL004
    # epoch-loop analogue): a once-per-batch fetch there is fine
    ("flexflow_tpu/serving/zz_ok_loop.py",
     "import jax\n\n"
     "class E:\n"
     "    def _dispatch_loop(self):\n"
     "        while self.running:\n"
     "            host = jax.device_get(self.step())\n"
     "            self.publish(host)\n",
     None),
    # outside flexflow_tpu/serving/ the rule does not engage
    ("flexflow_tpu/zz_ok_not_serving.py",
     "class E:\n"
     "    def _dispatch_batch(self, reqs):\n"
     "        for r in reqs:\n"
     "            r.set_result(float(r.x))\n",
     None),
    # RL010: a host sync inside a per-STREAM loop of the token-
    # generation decode path fences once per stream (ISSUE 11)
    ("flexflow_tpu/serving/generation/zz_bad_scatter.py",
     "class E:\n"
     "    def _decode_once(self):\n"
     "        out = self.step()\n"
     "        for s in self.streams:\n"
     "            s.emit(float(out))\n",
     "RL010"),
    # the sanctioned shape: ONE token fetch per decode step in
    # straight-line code, host values scattered in the loop
    ("flexflow_tpu/serving/generation/zz_ok_scatter.py",
     "import jax\n\n"
     "class E:\n"
     "    def _decode_once(self):\n"
     "        host = jax.device_get(self.step())\n"
     "        for i, s in enumerate(self.streams):\n"
     "            s.emit(int(host[i]))\n",
     None),
    # the boundary's ONE fetch (ISSUE 34): everything its flights left
    # on the device in one straight-line device_get, host values
    # scattered in the loops
    ("flexflow_tpu/serving/generation/zz_ok_land.py",
     "import jax\n\n"
     "class E:\n"
     "    def _land(self, *flights):\n"
     "        host = jax.device_get([(f.nxt, f.first) for f in flights])\n"
     "        for f, (nxt, first) in zip(flights, host):\n"
     "            self._deliver_step(f, nxt)\n"
     "    def _deliver_step(self, f, host):\n"
     "        for i, s in f.rows:\n"
     "            s.emit(int(host[i]))\n",
     None),
    # a fetch per flight, or per stream of the hand-over, is the
    # per-stream sync again
    ("flexflow_tpu/serving/generation/zz_bad_land.py",
     "import jax\n\n"
     "class E:\n"
     "    def _land(self, *flights):\n"
     "        for f in flights:\n"
     "            self._deliver_step(f, jax.device_get(f.nxt))\n",
     "RL010"),
    ("flexflow_tpu/serving/generation/zz_bad_deliver.py",
     "import numpy as np\n\n"
     "class E:\n"
     "    def _deliver_step(self, f, nxt):\n"
     "        for i, s in f.rows:\n"
     "            s.emit(int(np.asarray(nxt[i])))\n",
     "RL010"),
    # the join's first token fetched at once (a hand-off, a speculative
    # round) is straight-line in the chunk's function
    ("flexflow_tpu/serving/generation/zz_ok_chunk.py",
     "import jax\n\n"
     "class E:\n"
     "    def _run_chunk(self, slot, st):\n"
     "        first = self.prefill(slot)\n"
     "        if st.at_once:\n"
     "            st.tok = int(jax.device_get(first))\n",
     None),
    # the `while` decode loop is the per-step granularity (the RL005
    # serve-loop analogue)
    ("flexflow_tpu/serving/generation/zz_ok_loop.py",
     "import jax\n\n"
     "class E:\n"
     "    def _decode_loop(self):\n"
     "        while self.running:\n"
     "            host = jax.device_get(self.step())\n"
     "            self.publish(host)\n",
     None),
    # outside flexflow_tpu/serving/generation/ the rule does not
    # engage (the PARENT serving dir is RL005's scope, not RL010's)
    ("flexflow_tpu/serving/zz_ok_not_generation.py",
     "class E:\n"
     "    def _decode_once(self):\n"
     "        for s in self.streams:\n"
     "            s.emit(float(s.x))\n",
     None),
    # RL006: raw jax meshes outside parallel/mesh.py bypass the
    # reshard-aware MachineMesh factory (ISSUE 6)
    ("flexflow_tpu/zz_bad_mesh.py",
     "from jax.sharding import Mesh\n\n"
     "def f(devs):\n"
     "    return Mesh(devs, ('x',))\n",
     "RL006"),
    ("flexflow_tpu/serving/zz_bad_make_mesh.py",
     "import jax\n\n"
     "def f():\n"
     "    return jax.make_mesh((2,), ('n',))\n",
     "RL006"),
    # the factory itself is the sanctioned construction site
    ("flexflow_tpu/parallel/mesh.py",
     "from jax.sharding import Mesh\n\n"
     "def build(devs):\n"
     "    return Mesh(devs, ('n0',))\n",
     None),
    # MachineMesh use and test-side raw meshes are fine
    ("flexflow_tpu/zz_ok_machinemesh.py",
     "from flexflow_tpu.parallel.mesh import MachineMesh\n\n"
     "def f():\n"
     "    return MachineMesh({'n': 2})\n",
     None),
    ("tests/zz_ok_raw_mesh.py",
     "from jax.sharding import Mesh\n\n"
     "def f(devs):\n"
     "    return Mesh(devs, ('x',))\n",
     None),
    # RL008: serving code reads time ONLY through the injected clock —
    # a bare wall-clock call would rot the fake-clock overload tests
    ("flexflow_tpu/serving/zz_bad_clock.py",
     "import time\n\ndef age(self):\n    return time.monotonic() - self.t0\n",
     "RL008"),
    ("flexflow_tpu/serving/zz_bad_clock2.py",
     "import time\nT0 = time.time()\n",
     "RL008"),
    # default-argument position is the injection idiom, not a runtime
    # read (evaluated once at def time)
    ("flexflow_tpu/serving/zz_ok_clock_default.py",
     "import time\n\ndef f(t0=time.monotonic()):\n    return t0\n",
     None),
    # ...and referencing the function (no call) as the injectable
    # default is the standard clock= signature
    ("flexflow_tpu/serving/zz_ok_clock_ref.py",
     "import time\n\ndef f(clock=time.monotonic):\n    return clock()\n",
     None),
    # RL016: measurement lives in perfbench/ — a module named *bench*
    # inside the package is a finding wherever it sits (and no longer
    # exempt from the clock rule)
    ("flexflow_tpu/serving/bench.py",
     "import time\n\ndef t():\n    return time.monotonic()\n",
     "RL016"),
    ("flexflow_tpu/zz_train_bench.py",
     "import time\n\ndef t():\n    return time.monotonic()\n",
     "RL016"),
    ("perfbench/zz_train_bench.py",
     "import time\n\ndef t():\n    return time.monotonic()\n",
     None),
    # outside flexflow_tpu/serving/ the rule does not engage
    ("flexflow_tpu/zz_ok_clock_elsewhere.py",
     "import time\n\ndef t():\n    return time.time()\n",
     None),
    # RL009: a field annotated `# guarded_by: <lock>` read/written
    # outside a `with <lock>` block in the serving/elastic scope
    ("flexflow_tpu/serving/zz_bad_guard.py",
     "import threading\n\n"
     "class Q:\n"
     "    def __init__(self):\n"
     "        self._cv = threading.Condition()\n"
     "        self._rows = 0  # guarded_by: self._cv\n"
     "    def depth(self):\n"
     "        return self._rows\n",
     "RL009"),
    # ...taking the lock is the fix
    ("flexflow_tpu/serving/zz_ok_guard_with.py",
     "import threading\n\n"
     "class Q:\n"
     "    def __init__(self):\n"
     "        self._cv = threading.Condition()\n"
     "        self._rows = 0  # guarded_by: self._cv\n"
     "    def depth(self):\n"
     "        with self._cv:\n"
     "            return self._rows\n",
     None),
    # ...or the caller-holds helper contract on the def line
    ("flexflow_tpu/serving/zz_ok_guard_helper.py",
     "import threading\n\n"
     "class Q:\n"
     "    def __init__(self):\n"
     "        self._cv = threading.Condition()\n"
     "        self._rows = 0  # guarded_by: self._cv\n"
     "    def _pop(self):  # guarded_by: self._cv\n"
     "        self._rows -= 1\n"
     "    def take(self):\n"
     "        with self._cv:\n"
     "            self._pop()\n",
     None),
    # ...or the documented deliberate lock-free read
    ("flexflow_tpu/serving/zz_ok_guard_waiver.py",
     "import threading\n\n"
     "class Q:\n"
     "    def __init__(self):\n"
     "        self._cv = threading.Condition()\n"
     "        self._closed = False  # guarded_by: self._cv\n"
     "    def closed(self):\n"
     "        return self._closed  # unguarded-ok: racy read is benign\n",
     None),
    # a nested def (callback — may run on another thread) does NOT
    # inherit the enclosing with-block's lock
    ("flexflow_tpu/serving/zz_bad_guard_closure.py",
     "import threading\n\n"
     "class Q:\n"
     "    def __init__(self):\n"
     "        self._cv = threading.Condition()\n"
     "        self._rows = 0  # guarded_by: self._cv\n"
     "    def make_cb(self):\n"
     "        with self._cv:\n"
     "            def cb():\n"
     "                return self._rows\n"
     "        return cb\n",
     "RL009"),
    # elastic.py is in scope too
    ("flexflow_tpu/parallel/elastic.py",
     "import threading\n\n"
     "class S:\n"
     "    def __init__(self):\n"
     "        self._lock = threading.Lock()\n"
     "        self._hb = {}  # guarded_by: self._lock\n"
     "    def read(self):\n"
     "        return dict(self._hb)\n",
     "RL009"),
    # outside the serving/elastic scope the rule does not engage
    ("flexflow_tpu/zz_ok_guard_elsewhere.py",
     "import threading\n\n"
     "class Q:\n"
     "    def __init__(self):\n"
     "        self._cv = threading.Condition()\n"
     "        self._rows = 0  # guarded_by: self._cv\n"
     "    def depth(self):\n"
     "        return self._rows\n",
     None),
    # RL007: hardware-rate literals (bytes/s, FLOP/s band) in op/search
    # code are fossilized calibration numbers — they belong in
    # cost_model.DeviceSpec or the CalibrationTable (ISSUE 7)
    ("flexflow_tpu/ops/zz_bad_rate.py",
     "HBM_BW = 819e9\n",
     "RL007"),
    ("flexflow_tpu/search/zz_bad_rate.py",
     "def f():\n    return 2.5e10\n",
     "RL007"),
    # the annotated escape hatch for a legitimate site
    ("flexflow_tpu/ops/zz_ok_rate_annot.py",
     "PCIE_BW = 32e9  # RL007-ok: host-offload link, not a chip rate\n",
     None),
    # the device model and the calibration table are where rates LIVE
    ("flexflow_tpu/search/cost_model.py",
     "HBM_BW = 2765e9\n",
     None),
    ("flexflow_tpu/search/calibration.py",
     "X = 459e12\n",
     None),
    # outside ops/ and search/ the rule does not engage; neither do
    # sentinels/epsilons outside the rate band
    ("flexflow_tpu/zz_ok_rate_elsewhere.py",
     "B = 1e12\n",
     None),
    ("flexflow_tpu/search/zz_ok_small.py",
     "INF_SENTINEL = 1e29\nEPS = 1e-6\nn = 4096\n",
     None),
    # RL011: an event name not declared in obs/events.py vanishes
    # silently from every harvester (ISSUE 13)
    ("flexflow_tpu/zz_bad_event.py",
     "from .fflogger import get_logger\n\ndef f():\n"
     "    get_logger('serve').event('serve_statz', qps=1)\n",
     "RL011"),
    ("flexflow_tpu/zz_ok_event.py",
     "from .fflogger import get_logger\n\ndef f():\n"
     "    get_logger('serve').event('serve_stats', qps=1)\n",
     None),
    # a non-literal name needs the RL011-ok waiver naming its literals
    ("flexflow_tpu/zz_bad_event_var.py",
     "from .fflogger import get_logger\n\ndef f(name):\n"
     "    get_logger('serve').event(name, qps=1)\n",
     "RL011"),
    ("flexflow_tpu/zz_ok_event_var.py",
     "from .fflogger import get_logger\n\ndef f(name):\n"
     "    get_logger('serve').event(  # RL011-ok: serve_stats\n"
     "        name, qps=1)\n",
     None),
    # tests/scripts are out of RL011 scope (harnesses emit ad-hoc)
    ("tests/zz_ok_event_test.py",
     "from flexflow_tpu.fflogger import get_logger\n\ndef f():\n"
     "    get_logger('serve').event('totally_adhoc', x=1)\n",
     None),
    # RL013: a KV-shaped (rank >= 3) allocation in serving/generation/
    # outside pages.py bypasses the page pool the kv_memory accounting
    # (and the FF108/FF121/FF130 gates) integrate (ISSUE 15)
    ("flexflow_tpu/serving/generation/zz_bad_kv_alloc.py",
     "import jax.numpy as jnp\n\ndef f(pages, P, h, hd):\n"
     "    return jnp.zeros((pages, P, h, hd), jnp.float32)\n",
     "RL013"),
    ("flexflow_tpu/serving/generation/zz_bad_kv_alloc_np.py",
     "import numpy as np\n\ndef f(slots, seq, d):\n"
     "    return np.zeros((slots, seq, d), np.float32)\n",
     "RL013"),
    # pages.py IS the pool module — exempt
    ("flexflow_tpu/serving/generation/pages.py",
     "import jax.numpy as jnp\n\ndef alloc(shape):\n"
     "    return jnp.zeros((4, 16, 2, 16), jnp.float32)\n",
     None),
    # 1-D/2-D staging buffers (token rows, page tables) stay legal
    ("flexflow_tpu/serving/generation/zz_ok_staging.py",
     "import numpy as np\n\ndef f(slots, tpp):\n"
     "    return np.zeros((slots, tpp), np.int32)\n",
     None),
    # the waiver comment admits the rare legitimate site
    ("flexflow_tpu/serving/generation/zz_ok_waived_kv.py",
     "import numpy as np\n\ndef f():\n"
     "    return np.zeros((2, 2, 2))  # RL013-ok: host-side test rig\n",
     None),
    # outside serving/generation/ the rule does not engage
    ("flexflow_tpu/serving/zz_ok_dense_alloc.py",
     "import numpy as np\n\ndef f(n, s, d):\n"
     "    return np.zeros((n, s, d), np.float32)\n",
     None),
    # RL015: the generation stack and the KV accounting serve every
    # layer through the contract on Op and name no layer kind (ISSUE 29)
    ("flexflow_tpu/serving/generation/zz_bad_op_class.py",
     "from ...ops.rnn import LSTM\n\ndef f(op):\n"
     "    return isinstance(op, LSTM)\n",
     "RL015"),
    ("flexflow_tpu/analysis/kv_memory.py",
     "from ..op import Op, OpType\n\ndef f(op):\n"
     "    return op.op_type == OpType.ATTENTION\n",
     "RL015"),
    ("flexflow_tpu/serving/generation/zz_bad_op_type.py",
     "from ... import op as _op\n\ndef f(op):\n"
     "    return op.op_type is _op.OpType.LSTM\n",
     "RL015"),
    # asking the op is the sanctioned spelling
    ("flexflow_tpu/serving/generation/zz_ok_asks_the_op.py",
     "from ...op import Op, ServeStep\n\ndef f(op: Op, where: ServeStep):\n"
     "    return op.serve_state(2, 8, 16, None), op.position_wise\n",
     None),
    # quantize.py rewrites Linear weights and rightly knows Linear
    ("flexflow_tpu/serving/quantize.py",
     "from ..op import OpType\nfrom ..ops.linear import Linear\n\n"
     "def f(op):\n"
     "    return isinstance(op, Linear) or op.op_type == OpType.LINEAR\n",
     None),
    # RL014: unseeded RNG in serving code breaks the per-(seed,
    # request) sampling-determinism contract (ISSUE 16)
    ("flexflow_tpu/serving/zz_bad_np_random.py",
     "import numpy as np\n\ndef f():\n    return np.random.rand()\n",
     "RL014"),
    # (os.getpid, not time.time, keeps the pin orthogonal to RL008's
    # injected-clock rule, which also covers serving wall-clock reads)
    ("flexflow_tpu/serving/generation/zz_bad_pid_key.py",
     "import os\nimport jax\n\ndef f():\n"
     "    return jax.random.PRNGKey(os.getpid())\n",
     "RL014"),
    ("flexflow_tpu/serving/zz_bad_urandom_key.py",
     "import os\nimport jax\n\ndef f():\n"
     "    return jax.random.PRNGKey(\n"
     "        int.from_bytes(os.urandom(4), 'little'))\n",
     "RL014"),
    # seeded forms are the sanctioned spelling
    ("flexflow_tpu/serving/zz_ok_seeded_rng.py",
     "import numpy as np\n\ndef f(seed):\n"
     "    return np.random.default_rng(seed).random()\n",
     None),
    ("flexflow_tpu/serving/generation/zz_ok_seeded_key.py",
     "import jax\n\ndef f(seed):\n"
     "    return jax.random.PRNGKey(seed)\n",
     None),
    # the waiver comment admits the rare legitimate site
    ("flexflow_tpu/serving/zz_ok_waived_rng.py",
     "import os\nimport jax\n\ndef f():\n"
     "    return jax.random.PRNGKey(os.getpid())"
     "  # RL014-ok: per-process jitter\n",
     None),
    # outside serving/ the rule does not engage
    ("flexflow_tpu/zz_ok_rng_outside_serving.py",
     "import numpy as np\n\ndef f():\n    return np.random.rand()\n",
     None),
    # RL012: jnp.dtype() resolution in an op module bypasses the ONE
    # precision-resolution point (ops/common.py)
    ("flexflow_tpu/ops/zz_bad_dtype_call.py",
     "import jax.numpy as jnp\n\ndef f(ctx):\n"
     "    return jnp.dtype(ctx.compute_dtype)\n",
     "RL012"),
    # ...as does a raw dtype string literal
    ("flexflow_tpu/ops/zz_bad_dtype_str.py",
     "def f(x):\n    return x.astype('float32')\n",
     "RL012"),
    # ops/common.py IS the resolution point — exempt
    ("flexflow_tpu/ops/common.py",
     "import jax.numpy as jnp\n\ndef cast(x, ctx):\n"
     "    return x.astype(jnp.dtype(ctx.compute_dtype))\n",
     None),
    # symbolic jnp dtypes are the sanctioned semantic-pin spelling
    ("flexflow_tpu/ops/zz_ok_symbolic.py",
     "import jax.numpy as jnp\n\ndef f(x):\n"
     "    return x.astype(jnp.float32)\n",
     None),
    # the waiver comment admits the rare legitimate site
    ("flexflow_tpu/ops/zz_ok_waived.py",
     "import numpy as np\n\ndef f():\n"
     "    return np.dtype('int8').itemsize  # RL012-ok: host-side size\n",
     None),
    # outside ops/ the rule does not engage
    ("flexflow_tpu/zz_ok_outside_ops.py",
     "import jax.numpy as jnp\n\ndef f(x):\n"
     "    return x.astype(jnp.dtype('float32'))\n",
     None),
])
def test_repo_lint_rules(tmp_path, rel, src, code):
    """repo_lint unit check on synthetic files, laid out under tmp_path
    mirroring the repo so the path-scoped rules engage."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import repo_lint
    finally:
        sys.path.pop(0)
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    # patch the repo root so _rel() yields the mirrored relative path
    old = repo_lint.REPO
    repo_lint.REPO = str(tmp_path)
    try:
        findings = repo_lint.lint_file(str(path))
    finally:
        repo_lint.REPO = old
    if code is None:
        assert findings == [], findings
    else:
        assert findings and code in findings[0], findings


def test_repo_lint_clean_on_this_repo():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "repo_lint.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


# ----------------------------------------------------------------------
# a document names only what the checkout has (ISSUE 47): README.md and
# docs/*.md; the history files (CHANGES.md, ROADMAP.md, PERF.md,
# SURVEY.md) are not read
# ----------------------------------------------------------------------
_DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
_PATH_ROOTS = ("flexflow_tpu/", "scripts/", "artifacts/", "tests/",
               "perfbench/", "examples/", "docs/")
_ROOT_FILE = re.compile(r"[A-Za-z0-9_.-]+\.(py|md|json|sh)")
_CLI_WORD = r"flexflow-tpu\s+([a-z][a-z-]*)(?![\w./])"


@functools.lru_cache(maxsize=None)
def _tracked_files():
    r = subprocess.run(["git", "ls-files"], capture_output=True, text=True,
                       cwd=REPO, timeout=60)
    if r.returncode == 0 and r.stdout.strip():
        return frozenset(r.stdout.split("\n"))
    # a checkout without its .git: what is on disk is what there is
    return frozenset(os.path.relpath(os.path.join(d, f), REPO)
                     for d, _dirs, files in os.walk(REPO) for f in files)


@pytest.mark.parametrize("doc", _DOCS)
def test_every_path_a_document_names_exists(doc):
    """Every back-ticked repository path names a tracked file (or a
    directory that holds one), and every ``flexflow-tpu <word>`` a
    subcommand ``cli.main`` dispatches: a deleted module, script,
    artifact or subcommand leaves no sentence behind."""
    tracked = _tracked_files()
    names = {os.path.basename(f) for f in tracked}
    with open(os.path.join(REPO, "flexflow_tpu", "cli.py")) as f:
        subcommands = set(re.findall(r'argv\[0\] == "([a-z-]+)"', f.read()))
    with open(os.path.join(REPO, doc)) as f:
        lines = f.read().splitlines()
    code, prose, fenced = [], [], False
    for line in lines:
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif fenced or line.startswith("    "):
            code.append(line)
        else:
            prose.append(line)
    prose = "\n".join(prose)
    missing = []
    for tok in re.findall(r"`([^`\n]+)`", prose):
        if any(c in tok for c in "*<{") or "..." in tok or not tok.strip():
            continue
        path = re.sub(r"(::.*|:\d[\d,:-]*)$", "", tok.split()[0])
        if path.startswith(_PATH_ROOTS):
            ok = path in tracked or any(
                f.startswith(path.rstrip("/") + "/") for f in tracked)
        elif _ROOT_FILE.fullmatch(path):
            ok = path in names
        else:
            continue
        if not ok:
            missing.append(tok)
    words = re.findall("`" + _CLI_WORD, prose)
    words += re.findall(_CLI_WORD, "\n".join(code))
    missing += [f"flexflow-tpu {w}" for w in words if w not in subcommands]
    assert not missing, f"{doc} names what the checkout lacks: {missing}"
