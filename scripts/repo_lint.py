#!/usr/bin/env python
"""Custom AST lint enforcing repo invariants ruff cannot express.

Run by ``scripts/static_checks.sh`` (the repo static gate, also smoke-run
by tier-1 ``tests/test_static_checks.py``).  Rules:

* **RL001 — checkpoint writes go through ``resilience._atomic_savez``**:
  a bare ``np.savez``/``savez_compressed`` in ``flexflow_tpu/`` can leave
  a truncated file at the final name on a crash, which costs every
  elastic restart a verification-and-fallback pass (PR 2's atomic-publish
  contract).  Only ``flexflow_tpu/resilience.py`` may call it.
* **RL002 — no ``warnings.warn`` in strategy/sharding paths**: legality
  findings in ``flexflow_tpu/strategy/`` and
  ``flexflow_tpu/parallel/sharding.py`` must be structured diagnostics
  (``flexflow_tpu.analysis``) — per-trace warnings are unaggregated,
  unmachine-readable, and exactly the scattered-legality failure ISSUE 3
  unified away.
* **RL003 — no unseeded RNG in tests**: module-level ``random.*`` /
  ``np.random.*`` draws make failures irreproducible; tests must use
  ``np.random.default_rng(seed)`` / ``random.Random(seed)`` /
  ``jax.random.PRNGKey(seed)``.
* **RL004 — no per-step host syncs in train/eval batch loops**: inside
  the batch loops of ``fit``/``evaluate``/``predict`` in
  ``flexflow_tpu/``, a ``float(...)``, ``np.asarray(...)`` or
  ``jax.device_get(...)`` fences the async dispatch pipeline every
  batch (ISSUE 4's fused-dispatch fix: accumulate on device, fetch
  ONCE after the loop).  The per-EPOCH loop (``for epoch in ...``) is
  exempt — an epoch-boundary fetch is the intended sync point.
* **RL006 — device meshes are built ONLY in ``parallel/mesh.py``**: a
  ``jax.sharding.Mesh(...)`` / ``jax.make_mesh(...)`` constructed
  anywhere else in ``flexflow_tpu/`` bypasses ``MachineMesh`` — the
  reshard-aware mesh factory the live-resharding path (ISSUE 6)
  rebuilds state against.  A raw Mesh smuggled past it would keep
  working until the first ``reshard()``/resume-on-new-mesh, then
  silently disagree with the model's placement.  Tests may build raw
  meshes (they pin jax-level behavior).
* **RL007 — no hard-coded timing/bandwidth constants in op or search
  code**: a numeric literal in the hardware-rate band (1e8..1e16 —
  bytes/s, FLOP/s) inside ``flexflow_tpu/ops/`` or
  ``flexflow_tpu/search/`` is a fossilized calibration number the
  profile-calibrated cost model (ISSUE 7) exists to replace.  Rate
  constants live in ``search/cost_model.py`` (``DeviceSpec``) or the
  CalibrationTable (``search/calibration.py``) — both files exempt;
  the rare legitimate site elsewhere carries an ``RL007-ok:`` comment
  on the same line explaining why.
* **RL005 — no per-request host syncs in the serving dispatch path**
  (the serve-side mirror of RL004, ISSUE 5): inside the dispatch
  functions of ``flexflow_tpu/serving/`` (``_dispatch_loop`` /
  ``_dispatch_batch``), the engine's contract is ONE ``device_get``
  per packed batch, amortized over every coalesced request.  The
  straight-line per-batch fetch is sanctioned (as is the ``while``
  serve loop itself — the analogue of RL004's epoch loop); any
  ``float``/``np.asarray``/``jax.device_get`` inside a ``for`` loop
  there is a per-request sync and is rejected.
* **RL009 — lock-annotated fields are only touched under their lock**
  (ISSUE 9; ISSUE 12 extends the scope to ``serving/fleet/`` — the
  FleetEngine's tenant table and publish queue are annotated): a field
  assignment in ``flexflow_tpu/serving/`` (any depth, fleet included)
  or ``flexflow_tpu/parallel/elastic.py`` may carry a
  ``# guarded_by: self._cv`` comment; every OTHER read/write of that
  ``self.<field>`` in the same class must then sit lexically inside a
  ``with self._cv:`` block (condition variables acquire their lock), or
  in a helper whose ``def`` line carries the same ``# guarded_by:``
  annotation (the documented caller-holds-the-lock contract), or on a
  line annotated ``# unguarded-ok: <why>`` (the rare deliberate
  lock-free read — e.g. the engine's lock-free ``health`` property).
  ``__init__`` is exempt (no concurrent access before construction
  completes); nested functions start with NO held locks (a closure may
  run on another thread).  This is the static half of the overload
  stack's thread-safety story: the fake-clock tests exercise the
  schedules, RL009 pins the discipline.
* **RL010 — no host syncs in the token-generation decode loop**
  (the generation mirror of RL004/RL005, ISSUE 11; the loop's shape
  since ISSUE 34): inside the step boundary's functions of
  ``flexflow_tpu/serving/generation/`` (``_decode_loop`` /
  ``_run_boundary`` / ``_run_chunk`` / ``_decode_once`` / ``_land`` /
  ``_deliver_step``), the engine's contract is ONE fetch a boundary for
  the WHOLE decode batch — ``_land``'s straight-line ``device_get`` of
  what the boundary (or, one step ahead, the boundary before it) left
  on the device is sanctioned, as are the first token a hand-off or a
  speculative round needs at once (``_run_chunk``, straight-line) and
  the ``while`` decode loop (the analogue of the serve/epoch loops); a
  ``float``/``np.asarray``/``jax.device_get`` inside a ``for`` loop
  there is a per-stream sync and is rejected.
* **RL008 — serving code reads time only through the injected clock**
  (ISSUE 8): a bare ``time.time()``/``time.monotonic()`` call inside
  ``flexflow_tpu/serving/`` bypasses the ``clock=`` every serving
  class takes, and the deterministic fake-clock overload/deadline
  tests rot the moment one sneaks in — the code under test would mix
  fake and real time.  Default-argument position is exempt (``clock:
  Callable = time.monotonic`` and friends are the injection point
  itself).
* **RL012 — dtype resolution in op code happens in ONE place**
  (ISSUE 14): inside ``flexflow_tpu/ops/`` (``ops/common.py`` — the
  resolution point — exempt), a ``jnp.dtype(...)``/``np.dtype(...)``
  call or a dtype STRING literal ("float32", "bfloat16", ...) is a
  second dtype-policy site the per-op precision axis
  (``resolve_op_dtype``/``cast_compute``) cannot see.  Symbolic dtypes
  (``jnp.float32`` for pinned f32 accumulation/statistics) are the
  sanctioned spelling of a *semantic* pin and stay legal; the rare
  legitimate string/call site carries an ``RL012-ok:`` comment.
* **RL013 — KV pages are allocated ONLY through the page-pool module**
  (ISSUE 15): inside ``flexflow_tpu/serving/generation/`` (except
  ``pages.py`` — the sanctioned allocation site), a
  ``jnp.zeros``/``np.zeros``/``ones``/``empty``/``full`` call whose
  shape literal has >= 3 dims is a KV-shaped allocation bypassing
  ``pages.alloc_pool_arrays`` — a second allocation path whose bytes
  the ``analysis.kv_memory`` page-pool accounting (and therefore the
  FF108/FF121/FF130 gates) would never see.  1-D/2-D staging buffers
  (token rows, page tables) stay legal; the rare legitimate site
  carries an ``RL013-ok:`` comment.
* **RL011 — every emitted event name is declared in the registry**
  (ISSUE 13): a ``Category.event("name", ...)`` call site in
  ``flexflow_tpu/`` must pass a string literal declared in
  ``flexflow_tpu/obs/events.py`` — a typo'd name produces a valid
  JSON line every harvester (``calibrate``'s capture_events hook,
  the flight recorder) silently ignores.
  A non-literal name needs an ``RL011-ok:`` comment naming the
  literals it can resolve to (each declared).  ``fflogger.py`` (the
  definition site) and tests/scripts are out of scope.
* **RL014 — no unseeded RNG in serving code** (ISSUE 16): sampling in
  ``flexflow_tpu/serving/`` must be deterministic per (seed, request)
  — the whole reproducibility contract of the sampled decode path.
  Two leaks break it: a global-state ``np.random.<draw>()`` (use
  ``np.random.default_rng(seed)`` or the request's
  ``SamplingParams.seed``), and a ``jax.random.PRNGKey(...)`` whose
  argument is derived from wall-clock or process entropy
  (``time.time``/``time.monotonic``/``os.urandom``/``os.getpid``) —
  a key that differs between two identical runs.  The rare
  deliberate site carries an ``RL014-ok:`` comment.
* **RL015 — the generation stack knows no layer kind** (ISSUE 29): what
  a layer keeps between tokens, whether it can generate and how it
  advances are the op's own (``Op.serve_state`` / ``serve_check`` /
  ``serve_step``), so no module under
  ``flexflow_tpu/serving/generation/`` nor
  ``flexflow_tpu/analysis/kv_memory.py`` imports anything from
  ``flexflow_tpu.ops`` or names ``OpType`` — an ``isinstance`` arm or
  an op-type comparison there is the ladder a new layer kind would
  have to climb again.  (``serving/quantize.py`` rewrites ``Linear``
  weights and rightly knows ``Linear``; it is outside the rule.)  A
  deliberate site carries an ``RL015-ok:`` comment.
* **RL016 — measurement lives in ``perfbench/``** (ISSUE 47): a module
  under ``flexflow_tpu/`` whose file name contains ``bench`` is a
  harness inside the package.  The benchmark is ``BENCHMARK.json`` +
  ``perfbench/``; its results are ``PERF_LEDGER.jsonl``; a second place
  that times the system is a second answer nobody reads.

Exit 0 when clean, 1 with ``file:line: RLxxx message`` findings on
stdout.  No third-party deps — must run on a bare CPython.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# np.random module-level constructors/utilities that are NOT draws
_NP_RANDOM_OK = {"default_rng", "RandomState", "Generator", "seed",
                 "get_state", "set_state", "SeedSequence", "PCG64",
                 "Philox", "MT19937", "BitGenerator"}
# stdlib random module members that are not global-state draws
_PY_RANDOM_OK = {"Random", "SystemRandom", "seed", "getstate", "setstate"}


def _dotted(node: ast.AST) -> Optional[str]:
    """'np.random.randn' for Attribute chains rooted at a Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _rel(path: str) -> str:
    return os.path.relpath(path, REPO).replace(os.sep, "/")


# RL011: the declared event-name registry, parsed by AST from the REAL
# repo's flexflow_tpu/obs/events.py (not imported — the lint must run
# on a bare CPython, and not relative to a patched REPO root so the
# synthetic-file tests still validate against the true registry)
_EVENT_REGISTRY: Optional[frozenset] = None


def _declared_events() -> frozenset:
    global _EVENT_REGISTRY
    if _EVENT_REGISTRY is not None:
        return _EVENT_REGISTRY
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "flexflow_tpu", "obs", "events.py")
    names: set = set()
    try:
        with open(path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "EVENTS"
                            for t in node.targets)
                    and isinstance(node.value, ast.Dict)):
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        names.add(k.value)
    except (OSError, SyntaxError):
        pass  # registry unreadable: RL011 reports nothing rather than
        #       flagging every event site with a bogus finding
    _EVENT_REGISTRY = frozenset(names)
    return _EVENT_REGISTRY


# host-sync call sites banned inside fit/evaluate/predict batch loops
# (RL004): each fences the device queue when applied to a live jax array
_RL004_BANNED = {"float", "np.asarray", "numpy.asarray", "jax.device_get",
                 "jax.block_until_ready"}
_RL004_FUNCS = ("fit", "evaluate", "predict")
# the serving dispatch functions RL005 scopes to (same banned set): the
# engine fetches once per packed batch in straight-line code; for-loops
# inside these iterate requests
_RL005_FUNCS = ("_dispatch_loop", "_dispatch_batch")
# the token-generation step boundary's functions RL010 scopes to (same
# banned set): one fetch a boundary in straight-line code (`_land`);
# for-loops inside these iterate streams/slots/flights
_RL010_FUNCS = ("_decode_loop", "_run_boundary", "_run_chunk",
                "_decode_once", "_land", "_deliver_step")

# wall-clock reads RL008 bans in flexflow_tpu/serving/ (outside
# default-argument position): every serving class takes an injectable
# ``clock=`` — the fake-clock overload tests depend on it being the
# ONLY time source
_RL008_BANNED = {"time.time", "time.monotonic"}


# RL012: dtype string literals banned in flexflow_tpu/ops/ outside the
# one resolution module (ops/common.py) — string dtypes there bypass
# the per-op precision axis's single resolution point
_RL012_EXEMPT = ("flexflow_tpu/ops/common.py",)
_RL012_DTYPE_STRINGS = {
    "float16", "float32", "float64", "bfloat16",
    "int8", "int16", "int32", "int64", "uint8", "bool",
}

# files where hardware-rate literals are the DESIGN (the device model
# and the calibration table) — exempt from RL007
_RL007_EXEMPT = ("flexflow_tpu/search/cost_model.py",
                 "flexflow_tpu/search/calibration.py")
# the bytes/s-to-FLOP/s magnitude band RL007 polices (ici/dcn/hbm
# bandwidths are 1e9-1e12, MXU flops ~1e14; sentinels like 1e29 and
# epsilons are far outside)
_RL007_LO, _RL007_HI = 1e8, 1e16


# RL013: the one sanctioned KV allocation site under serving/generation/
_RL013_POOL_MODULE = "flexflow_tpu/serving/generation/pages.py"
_RL013_ALLOC_LEAVES = {"zeros", "ones", "empty", "full"}
_RL013_ALLOC_ROOTS = {"jnp", "np", "numpy", "jax.numpy"}


# `# guarded_by: self._cv` (field or def-line) / `# unguarded-ok: why`
_GUARDED_RE = re.compile(r"#\s*guarded_by:\s*([\w.]+)")
_UNGUARDED_RE = re.compile(r"#\s*unguarded-ok\b")


class _GuardChecker(ast.NodeVisitor):
    """RL009 — per-class lock-discipline check.  Pass 1 collects
    ``self.<field> = ...  # guarded_by: <lock>`` annotations; pass 2
    walks every method tracking which locks are lexically held
    (``with <lock>:`` blocks, plus a ``# guarded_by:`` annotation on
    the ``def`` line for caller-holds helpers) and flags annotated-field
    accesses outside them."""

    def __init__(self, lines, add):
        self.lines = lines
        self._add = add
        self.fields = {}        # field name -> lock dotted name
        self._held = frozenset()
        self._checking = False

    def _line(self, node) -> str:
        return (self.lines[node.lineno - 1]
                if 0 < node.lineno <= len(self.lines) else "")

    def check_class(self, cls: ast.ClassDef) -> None:
        # pass 1: collect annotated fields (any `self.X =` whose line
        # carries the guarded_by comment)
        for node in ast.walk(cls):
            if not isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign)):
                continue
            m = _GUARDED_RE.search(self._line(node))
            if not m:
                continue
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    self.fields[t.attr] = m.group(1)
        if not self.fields:
            return
        # pass 2: check every method except __init__ (single-threaded
        # construction — it is where the annotations live)
        for node in cls.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name != "__init__":
                self._check_func(node)

    def _check_func(self, fn) -> None:
        held = set()
        m = _GUARDED_RE.search(self._line(fn))
        if m:
            held.add(m.group(1))  # caller-holds contract
        prev, self._held = self._held, frozenset(held)
        was, self._checking = self._checking, True
        for stmt in fn.body:
            self.visit(stmt)
        self._held, self._checking = prev, was

    def visit_With(self, node: ast.With) -> None:
        names = set()
        for item in node.items:
            d = _dotted(item.context_expr)
            if d is None and isinstance(item.context_expr, ast.Call):
                d = _dotted(item.context_expr.func)
            if d:
                names.add(d)
        for item in node.items:
            self.visit(item.context_expr)
        prev, self._held = self._held, self._held | names
        for stmt in node.body:
            self.visit(stmt)
        self._held = prev

    visit_AsyncWith = visit_With

    def visit_FunctionDef(self, node) -> None:
        # a nested `def` (callback/closure) may run on another thread:
        # it starts with NO held locks.  Lambdas inherit the current
        # held set — the sort-key/filter lambda evaluated synchronously
        # under the caller's lock is the overwhelmingly common case.
        self._check_func(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (self._checking and isinstance(node.value, ast.Name)
                and node.value.id == "self"
                and node.attr in self.fields):
            lock = self.fields[node.attr]
            if lock not in self._held \
                    and not _UNGUARDED_RE.search(self._line(node)):
                self._add(node, "RL009",
                          f"self.{node.attr} is annotated guarded_by "
                          f"{lock} but accessed outside a `with {lock}` "
                          f"block — take the lock, mark the helper's "
                          f"def line `# guarded_by: {lock}` (caller "
                          f"holds), or annotate the line "
                          f"`# unguarded-ok: <why>`")
        self.generic_visit(node)


class _Visitor(ast.NodeVisitor):
    def __init__(self, relpath: str, lines: Optional[List[str]] = None):
        self.relpath = relpath
        self.lines = lines or []
        self.findings: List[Tuple[int, str, str]] = []
        self.in_library = relpath.startswith("flexflow_tpu/")
        self.in_rate_scope = (
            (relpath.startswith("flexflow_tpu/ops/")
             or relpath.startswith("flexflow_tpu/search/"))
            and relpath not in _RL007_EXEMPT)
        self.is_resilience = relpath == "flexflow_tpu/resilience.py"
        self.in_diag_scope = (
            relpath.startswith("flexflow_tpu/strategy/")
            or relpath == "flexflow_tpu/parallel/sharding.py")
        self.in_tests = relpath.startswith("tests/")
        self.in_serving = relpath.startswith("flexflow_tpu/serving/")
        # RL012: op modules resolve dtypes through ops/common.py only
        self.in_ops_dtype_scope = (
            relpath.startswith("flexflow_tpu/ops/")
            and relpath not in _RL012_EXEMPT)
        self.in_generation = relpath.startswith(
            "flexflow_tpu/serving/generation/")
        # RL015: where a layer kind must not be known
        self.in_layer_blind_scope = (
            self.in_generation
            or relpath == "flexflow_tpu/analysis/kv_memory.py")
        # RL009 engages where the concurrency-heavy classes live: the
        # serving stack (incl. generation/), the elastic supervisor and
        # the observability plane (ISSUE 18 widened it to obs/ so the
        # annotation lint covers the same ground fflock inference does)
        self.in_guard_scope = (self.in_serving
                               or relpath.startswith("flexflow_tpu/obs/")
                               or relpath == "flexflow_tpu/parallel/"
                                              "elastic.py")
        self.is_mesh_factory = relpath == "flexflow_tpu/parallel/mesh.py"
        self._hot_func: Optional[str] = None  # inside fit/evaluate/predict
        self._batch_loops = 0                 # nested non-epoch loop depth
        self._serve_func: Optional[str] = None  # inside _dispatch_*
        self._req_loops = 0                   # nested for-loop depth there
        self._gen_func: Optional[str] = None  # inside _decode_* (RL010)
        self._gen_loops = 0                   # nested for-loop depth there
        self._default_pos: set = set()        # Call nodes in arg defaults

    def _add(self, node: ast.AST, code: str, msg: str) -> None:
        self.findings.append((node.lineno, code, msg))

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        if self.in_guard_scope:
            _GuardChecker(self.lines, self._add).check_class(node)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if name:
            self._check_savez(node, name)
            self._check_warn(node, name)
            self._check_rng(node, name)
            self._check_serving_rng(node, name)
            self._check_step_sync(node, name)
            self._check_raw_mesh(node, name)
            self._check_clock(node, name)
            self._check_dtype_call(node, name)
            self._check_kv_alloc(node, name)
        self._check_event_name(node)
        self.generic_visit(node)

    def _check_kv_alloc(self, node: ast.Call, name: str) -> None:
        """RL013: KV-shaped (rank >= 3) array allocations under
        serving/generation/ happen in pages.py ONLY — a second
        allocation site cannot be seen by the kv_memory page-pool
        accounting the FF108/FF121/FF130 gates charge."""
        if (not self.in_generation
                or self.relpath == _RL013_POOL_MODULE):
            return
        root, _, leaf = name.rpartition(".")
        if leaf not in _RL013_ALLOC_LEAVES \
                or root not in _RL013_ALLOC_ROOTS:
            return
        if not node.args:
            return
        shape = node.args[0]
        if not isinstance(shape, (ast.Tuple, ast.List)) \
                or len(shape.elts) < 3:
            return  # 1-D/2-D staging buffers (token rows, page tables)
        line = (self.lines[node.lineno - 1]
                if 0 < node.lineno <= len(self.lines) else "")
        if "RL013-ok" not in line:
            self._add(node, "RL013",
                      f"{name}() with a rank-{len(shape.elts)} shape in "
                      f"serving/generation/ — KV pages are allocated "
                      f"only through pages.alloc_pool_arrays (the "
                      f"analysis.kv_memory-accounted pool); a raw "
                      f"KV-shaped buffer here is HBM the FF108/FF121/"
                      f"FF130 gates never see.  Annotate 'RL013-ok: "
                      f"why' if this site is legitimate")

    def _check_dtype_call(self, node: ast.Call, name: str) -> None:
        """RL012 (call half): jnp.dtype()/np.dtype() in op modules is a
        second dtype-resolution site — route through ops/common.py
        (resolve_op_dtype / cast_compute / dtype_itemsize)."""
        if not self.in_ops_dtype_scope:
            return
        if name in ("jnp.dtype", "np.dtype", "numpy.dtype",
                    "jax.numpy.dtype"):
            line = (self.lines[node.lineno - 1]
                    if 0 < node.lineno <= len(self.lines) else "")
            if "RL012-ok" not in line:
                self._add(node, "RL012",
                          f"{name}() in flexflow_tpu/ops/ — dtype "
                          f"resolution lives in ops/common.py only "
                          f"(resolve_op_dtype/cast_compute/"
                          f"dtype_itemsize), so the per-op precision "
                          f"axis has ONE policy point; annotate "
                          f"'RL012-ok: why' if this site is legitimate")

    def _check_event_name(self, node: ast.Call) -> None:
        """RL011: ``<logger>.event(<name>, ...)`` call sites in the
        library must pass a string literal declared in
        flexflow_tpu/obs/events.py (fflogger.py — the definition site —
        is exempt, as are tests/scripts)."""
        if (not self.in_library
                or self.relpath == "flexflow_tpu/fflogger.py"
                or not isinstance(node.func, ast.Attribute)
                or node.func.attr != "event"
                or not node.args):
            return
        registry = _declared_events()
        if not registry:
            return
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            if arg.value not in registry:
                self._add(node, "RL011",
                          f"event name {arg.value!r} is not declared in "
                          f"flexflow_tpu/obs/events.py — undeclared "
                          f"names vanish silently from every harvester; "
                          f"declare it (one line + contract) or fix the "
                          f"typo")
            return
        # non-literal name: allowed only with an RL011-ok waiver that
        # names the declared literals it resolves to
        for ln in range(node.lineno,
                        min(len(self.lines), node.lineno + 3) + 1):
            if "RL011-ok" in (self.lines[ln - 1]
                              if 0 < ln <= len(self.lines) else ""):
                return
        self._add(node, "RL011",
                  "non-literal event name — every Category.event call "
                  "site must pass a declared literal (obs/events.py), "
                  "or carry an 'RL011-ok: <literals>' comment when the "
                  "name is a validated parameter")

    def _layer_kind(self, node: ast.AST, what: str) -> None:
        """RL015: a reference to an op class or an op type where the
        code serves every layer through the contract on ``Op``."""
        line = (self.lines[node.lineno - 1]
                if 0 < node.lineno <= len(self.lines) else "")
        if "RL015-ok" not in line:
            self._add(node, "RL015",
                      f"{what} in {self.relpath} — the generation stack "
                      f"asks the op (Op.serve_state / serve_check / "
                      f"serve_step) and names no layer kind; put the "
                      f"decision behind the op, or annotate "
                      f"'RL015-ok: why' if this site is legitimate")

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if self.in_layer_blind_scope:
            mod = node.module or ""
            if mod == "ops" or mod.startswith(("ops.", "flexflow_tpu.ops")) \
                    or any(a.name == "ops" for a in node.names):
                self._layer_kind(node, "import from flexflow_tpu.ops")
            elif any(a.name == "OpType" for a in node.names):
                self._layer_kind(node, "OpType imported")
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        if self.in_layer_blind_scope and any(
                a.name.startswith("flexflow_tpu.ops") for a in node.names):
            self._layer_kind(node, "import of flexflow_tpu.ops")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.in_layer_blind_scope and (
                node.attr == "OpType"
                or (isinstance(node.value, ast.Name)
                    and node.value.id == "OpType")):
            self._layer_kind(node, "an OpType named")
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        v = node.value
        if self.in_ops_dtype_scope and isinstance(v, str) \
                and v in _RL012_DTYPE_STRINGS:
            line = (self.lines[node.lineno - 1]
                    if 0 < node.lineno <= len(self.lines) else "")
            if "RL012-ok" not in line:
                self._add(node, "RL012",
                          f"dtype string literal {v!r} in "
                          f"flexflow_tpu/ops/ — spell dtype policy "
                          f"through ops/common.py (F32/BF16 constants, "
                          f"resolve_op_dtype) or a symbolic jnp dtype; "
                          f"annotate 'RL012-ok: why' if legitimate")
        if self.in_rate_scope and isinstance(v, (int, float)) \
                and not isinstance(v, bool) \
                and _RL007_LO <= abs(v) < _RL007_HI:
            line = (self.lines[node.lineno - 1]
                    if 0 < node.lineno <= len(self.lines) else "")
            if "RL007-ok" not in line:
                self._add(node, "RL007",
                          f"hardware-rate literal {v!r} outside "
                          f"cost_model.DeviceSpec / the calibration "
                          f"table — measured rates belong in the "
                          f"CalibrationTable (flexflow-tpu calibrate), "
                          f"spec-sheet rates in DeviceSpec; annotate "
                          f"'RL007-ok: why' if this site is legitimate")
        self.generic_visit(node)

    def _check_raw_mesh(self, node: ast.Call, name: str) -> None:
        if not self.in_library or self.is_mesh_factory:
            return
        leaf = name.rsplit(".", 1)[-1]
        if leaf in ("Mesh", "make_mesh"):
            self._add(node, "RL006",
                      f"raw {name}() outside parallel/mesh.py — build "
                      f"device meshes through MachineMesh so the live-"
                      f"reshard path (FFModel.reshard, reshard-on-"
                      f"resume) sees every mesh the repo constructs")

    def _check_clock(self, node: ast.Call, name: str) -> None:
        if not self.in_serving or name not in _RL008_BANNED:
            return
        if id(node) in self._default_pos:
            # `def f(now=time.monotonic())` evaluates ONCE at def time —
            # that's the injection-default idiom, not a runtime read
            return
        self._add(node, "RL008",
                  f"bare {name}() in flexflow_tpu/serving/ — serving "
                  f"code must read time through the injected clock "
                  f"(clock=...) so the deterministic fake-clock "
                  f"overload/deadline tests stay honest "
                  f"(docs/serving.md)")

    # --- RL004/RL005 scope tracking -----------------------------------
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # register Call nodes inside argument defaults before walking:
        # RL008 exempts default-argument position
        args = node.args
        for d in list(args.defaults) + [d for d in args.kw_defaults if d]:
            for sub in ast.walk(d):
                if isinstance(sub, ast.Call):
                    self._default_pos.add(id(sub))
        hot = (self.in_library and node.name in _RL004_FUNCS)
        serve = (self.in_serving and node.name in _RL005_FUNCS)
        gen = (self.in_generation and node.name in _RL010_FUNCS)
        prev = (self._hot_func, self._batch_loops,
                self._serve_func, self._req_loops,
                self._gen_func, self._gen_loops)
        if hot:
            self._hot_func, self._batch_loops = node.name, 0
        if serve:
            self._serve_func, self._req_loops = node.name, 0
        if gen:
            self._gen_func, self._gen_loops = node.name, 0
        self.generic_visit(node)
        (self._hot_func, self._batch_loops,
         self._serve_func, self._req_loops,
         self._gen_func, self._gen_loops) = prev

    visit_AsyncFunctionDef = visit_FunctionDef

    def _visit_loop(self, node) -> None:
        # the per-EPOCH loop is the sanctioned once-per-epoch sync point;
        # every other loop in a hot function iterates batches/windows
        target = getattr(node, "target", None)
        is_epoch = isinstance(target, ast.Name) and target.id == "epoch"
        scoped = self._hot_func is not None and not is_epoch
        # RL005 scopes FOR loops only: in the dispatch functions they
        # iterate requests, while the `while` serve loop is the
        # sanctioned once-per-packed-batch granularity (the analogue of
        # the epoch loop above)
        serve_scoped = (self._serve_func is not None
                        and isinstance(node, ast.For))
        # RL010 mirrors RL005: for-loops in the decode functions
        # iterate streams/slots; the while decode loop is the
        # once-per-step granularity
        gen_scoped = (self._gen_func is not None
                      and isinstance(node, ast.For))
        # a For's iter expression runs ONCE per loop entry (e.g.
        # `for s in jax.device_get(sums):` is the once-after-the-loop
        # idiom) — scan it OUTSIDE the batch-loop scope
        if isinstance(node, ast.For):
            self.visit(node.target)
            self.visit(node.iter)
        if scoped:
            self._batch_loops += 1
        if serve_scoped:
            self._req_loops += 1
        if gen_scoped:
            self._gen_loops += 1
        # a While's test RE-EVALUATES every iteration (`while
        # float(loss) > tol:` fences per iteration) — scan it INSIDE
        if isinstance(node, ast.While):
            self.visit(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)
        if scoped:
            self._batch_loops -= 1
        if serve_scoped:
            self._req_loops -= 1
        if gen_scoped:
            self._gen_loops -= 1

    visit_For = _visit_loop
    visit_While = _visit_loop

    def _check_step_sync(self, node: ast.Call, name: str) -> None:
        if name not in _RL004_BANNED:
            return
        if self._hot_func is not None and self._batch_loops > 0:
            self._add(node, "RL004",
                      f"{name}() inside the {self._hot_func}() batch loop "
                      f"fences the async dispatch pipeline every batch — "
                      f"keep sums/outputs on device and fetch once after "
                      f"the loop (docs/performance.md)")
        if self._serve_func is not None and self._req_loops > 0:
            self._add(node, "RL005",
                      f"{name}() inside a {self._serve_func}() request "
                      f"loop is a per-request host sync — fetch ONCE per "
                      f"packed batch and scatter host slices "
                      f"(docs/serving.md)")
        if self._gen_func is not None and self._gen_loops > 0:
            self._add(node, "RL010",
                      f"{name}() inside a {self._gen_func}() stream "
                      f"loop is a per-stream host sync — a step "
                      f"boundary fetches ONCE for the whole batch "
                      f"(_land) and scatters host values "
                      f"(docs/serving.md 'Token generation')")

    def _check_savez(self, node: ast.Call, name: str) -> None:
        if not self.in_library or self.is_resilience:
            return
        leaf = name.rsplit(".", 1)[-1]
        if leaf in ("savez", "savez_compressed"):
            self._add(node, "RL001",
                      f"direct {name}() — checkpoint writes must go "
                      f"through resilience._atomic_savez (atomic "
                      f"tmp+rename publish)")

    def _check_warn(self, node: ast.Call, name: str) -> None:
        if self.in_diag_scope and name == "warnings.warn":
            self._add(node, "RL002",
                      "warnings.warn in a strategy/sharding path — emit "
                      "a structured diagnostic via flexflow_tpu.analysis "
                      "instead")

    def _check_rng(self, node: ast.Call, name: str) -> None:
        if not self.in_tests:
            return
        parts = name.split(".")
        if parts[:2] in (["np", "random"], ["numpy", "random"]) \
                and len(parts) == 3 and parts[2] not in _NP_RANDOM_OK:
            self._add(node, "RL003",
                      f"unseeded global-state {name}() in a test — use "
                      f"np.random.default_rng(seed)")
        elif parts[0] == "random" and len(parts) == 2 \
                and parts[1] not in _PY_RANDOM_OK:
            self._add(node, "RL003",
                      f"unseeded global-state {name}() in a test — use "
                      f"random.Random(seed)")

    # RL014: entropy sources that make a PRNG key differ between two
    # identical serving runs
    _RL014_ENTROPY = {"time.time", "time.monotonic", "time.time_ns",
                      "time.perf_counter", "os.urandom", "os.getpid",
                      "uuid.uuid4", "secrets.token_bytes"}

    def _check_serving_rng(self, node: ast.Call, name: str) -> None:
        """RL014: serving code (the sampled decode path above all) must
        be deterministic per (seed, request) — no global-state numpy
        draws, no wall-clock/entropy-derived jax PRNG keys."""
        if not self.in_serving:
            return
        parts = name.split(".")
        if parts[:2] in (["np", "random"], ["numpy", "random"]) \
                and len(parts) == 3 and parts[2] not in _NP_RANDOM_OK:
            if "RL014-ok" not in self.lines[node.lineno - 1]:
                self._add(node, "RL014",
                          f"unseeded global-state {name}() in serving "
                          f"code — sampled decode must be deterministic "
                          f"per (seed, request); use np.random."
                          f"default_rng(seed) or the request's "
                          f"SamplingParams.seed")
            return
        if parts[-1] != "PRNGKey" and name != "PRNGKey":
            return
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if not isinstance(sub, ast.Call):
                    continue
                src = _dotted(sub.func)
                if src in self._RL014_ENTROPY:
                    if "RL014-ok" in self.lines[node.lineno - 1]:
                        return
                    self._add(node, "RL014",
                              f"PRNG key seeded from {src}() in serving "
                              f"code — the key differs between two "
                              f"identical runs, breaking per-(seed, "
                              f"request) reproducibility; derive keys "
                              f"from SamplingParams.seed")
                    return


def lint_file(path: str) -> List[str]:
    rel = _rel(path)
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{rel}:{e.lineno or 0}: RL000 syntax error: {e.msg}"]
    v = _Visitor(rel, src.splitlines())
    if v.in_library and "bench" in os.path.basename(rel):
        v.findings.append((1, "RL016",
                           "a module named *bench* under flexflow_tpu/ "
                           "— measurement lives in perfbench/"))
    v.visit(tree)
    return [f"{rel}:{ln}: {code} {msg}"
            for ln, code, msg in sorted(v.findings)]


def iter_py(roots: List[str]) -> List[str]:
    out = []
    for root in roots:
        if os.path.isfile(root):
            out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            out.extend(os.path.join(dirpath, f)
                       for f in filenames if f.endswith(".py"))
    return sorted(out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    roots = argv or [os.path.join(REPO, "flexflow_tpu"),
                     os.path.join(REPO, "tests"),
                     os.path.join(REPO, "scripts")]
    findings: List[str] = []
    for path in iter_py(roots):
        findings.extend(lint_file(path))
    for f in findings:
        print(f)
    if findings:
        print(f"repo_lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
