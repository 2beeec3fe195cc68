"""Profile-calibrated cost model — the sim-to-silicon loop.

The MCMC search (mcmc.py) optimizes whatever the simulator says, and the
simulator's analytic roofline (cost_model.py) had never been reconciled
against what XLA actually runs on the attached device — every search
"win" was a claim about the simulator, not the hardware.  This module
closes that loop the way "A Learned Performance Model for TPUs"
(arXiv 2008.01040) and "Learning to Optimize Tensor Programs"
(arXiv 1805.08166) prescribe: measure real op/dispatch timings, fit a
correction over op features, and feed the calibrated model back into the
search.

Three layers:

* :class:`CalibrationTable` — a versioned on-disk record of measured
  timings, keyed ``op-type × shape-bucket × dtype × partition-degree``,
  with device-kind and content-digest metadata.  Harvested from
  - the per-op microbench path (``profiling.profile_op``, the same
    slope-timed isolated-op measurement the simulator's measure mode
    uses), and
  - the per-dispatch wall times of the ``StepTraceAnnotation``-wrapped
    train/serve loops (fit()'s ``dispatch_ms`` epoch events; the
    serving engine's per-bucket ``dispatch_ms`` percentiles).
  The fossilized round-5 TPU v5 lite measurements that used to live in
  comments across ``ops/conv.py``/``ops/attention.py`` are now seed
  DATA: ``calibration_seed.json``, loaded by :func:`default_table`.

* :class:`CostEstimator` — the pluggable per-op time model the
  :class:`~flexflow_tpu.search.simulator.Simulator` consults.
  ``AnalyticEstimator`` reproduces ``op_compute_time`` bit-for-bit (an
  uncalibrated run — ``estimator=None`` — never constructs one, so the
  default path is literally unchanged).  ``TableEstimator`` rescales the
  analytic time by the measured/analytic ratio of the nearest table
  entry.  ``RidgeEstimator`` fits a ridge regression over op features
  (FLOPs, bytes in/out, fan-in/out, partition degrees — the 2008.01040
  feature set) in log space and predicts absolute times.

* the ``flexflow-tpu calibrate`` CLI — harvest a table from the model
  zoo and validate it (``--check``: schema + digest).

Comm-side calibration threads through :func:`calibrated_spec`: a table
may carry ``DeviceSpec`` field overrides (measured effective bandwidths)
and an ``xla_temp_factor``; rebuilding the Simulator/verifier spec from
them rescales ``transfer_time``/``allreduce_time`` and the FF108 HBM
pass consistently — the native sim engine receives the same spec
numbers, so every consumer sees one calibrated cost model.

This module (like cost_model.py) is exempt from repo_lint RL007 — it is
where timing data is ALLOWED to live.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .cost_model import DeviceSpec, op_compute_time, spec_for_device

SCHEMA_VERSION = 1
TABLE_KIND = "calibration_table"

_SEED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "calibration_seed.json")


# ---------------------------------------------------------------------------
# keys and features
# ---------------------------------------------------------------------------

def _pow2(n: int) -> int:
    """Smallest power of two >= max(1, n)."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def shape_bucket(shape: Sequence[int]) -> str:
    """Per-dim power-of-two bucket string, e.g. ``(24, 35, 100)`` ->
    ``"32x64x128"`` — nearby shapes share a bucket (and therefore a
    calibration entry) without collapsing rank or aspect ratio."""
    return "x".join(str(_pow2(s)) for s in shape)


def table_key(op_type: str, out_shape: Sequence[int], dtype: str,
              nparts: int) -> str:
    """The calibration key: op-type × shape-bucket × dtype ×
    partition-degree.  ``out_shape`` is the op's FULL (logical) output
    shape; ``nparts`` the product of the partition degrees — the same
    pair the simulator holds when it asks for the op's per-partition
    time, so harvest and lookup can never disagree."""
    return f"{op_type}|{shape_bucket(out_shape)}|{dtype}|p{int(nparts)}"


def _nparts(dims: Sequence[int]) -> int:
    n = 1
    for d in dims:
        n *= int(d)
    return max(1, n)


def op_key(op, dims: Sequence[int], dtype: str) -> str:
    return table_key(op.op_type.value, op.outputs[0].shape, dtype,
                     _nparts(dims))


def op_features(op, dims: Sequence[int]) -> Dict[str, float]:
    """The 2008.01040-style feature vector of one (op, partitioning):
    total FLOPs, element counts in/out, weight elements, fan-in/out and
    the partition degree.  Stored per table entry so a learned estimator
    can be (re)fit from the table alone, without the ops in hand."""
    nparts = _nparts(dims)
    return {
        "flops": float(op.flops()),
        "in_elems": float(sum(t.volume for t in op.inputs)),
        "out_elems": float(sum(t.volume for t in op.outputs)),
        "weight_elems": float(sum(w.volume for w in op.weights)),
        "fan_in": float(len(op.inputs)),
        "fan_out": float(len(op.outputs)),
        "nparts": float(nparts),
        "out_volume": float(op.outputs[0].volume),
    }


# ---------------------------------------------------------------------------
# the on-disk table
# ---------------------------------------------------------------------------

class CalibrationTable:
    """Measured-timing record: ``ops[key] = {features, fwd, bwd}`` with
    ``{analytic_ms, measured_ms, n}`` per direction (running means over
    ``n`` merged samples), plus per-dispatch entries from the train/serve
    loops, optional DeviceSpec overrides, and digest/device metadata."""

    def __init__(self, device_kind: str = "unknown",
                 compute_dtype: str = "bfloat16",
                 source: str = "flexflow-tpu calibrate"):
        self.version = SCHEMA_VERSION
        self.device_kind = device_kind
        self.compute_dtype = compute_dtype
        self.source = source
        self.spec: Dict[str, float] = {}
        self.xla_temp_factor: Optional[float] = None
        self.ops: Dict[str, Dict] = {}
        self.dispatch: Dict[str, Dict] = {}
        # optional dispatch-level power-law correction (fit_step_correction)
        self.step_correction: Optional[Dict] = None

    # -- mutation ----------------------------------------------------
    @staticmethod
    def _merge(rec: Optional[Dict], analytic_ms: float, measured_ms: float,
               n: int = 1) -> Dict:
        if rec is None:
            return {"analytic_ms": float(analytic_ms),
                    "measured_ms": float(measured_ms), "n": int(n)}
        tot = rec["n"] + n
        rec = dict(rec)
        rec["measured_ms"] = (rec["measured_ms"] * rec["n"]
                              + measured_ms * n) / tot
        rec["analytic_ms"] = (rec["analytic_ms"] * rec["n"]
                              + analytic_ms * n) / tot
        rec["n"] = tot
        return rec

    def add_op_sample(self, key: str, features: Dict[str, float],
                      fwd_analytic_ms: float, fwd_measured_ms: float,
                      bwd_analytic_ms: Optional[float] = None,
                      bwd_measured_ms: Optional[float] = None,
                      n: int = 1) -> None:
        entry = self.ops.get(key) or {"features": dict(features),
                                      "fwd": None, "bwd": None}
        entry["fwd"] = self._merge(entry["fwd"], fwd_analytic_ms,
                                   fwd_measured_ms, n)
        if bwd_measured_ms is not None and bwd_analytic_ms is not None \
                and bwd_measured_ms == bwd_measured_ms:  # not NaN
            entry["bwd"] = self._merge(entry["bwd"], bwd_analytic_ms,
                                       bwd_measured_ms, n)
        self.ops[key] = entry

    def add_dispatch_sample(self, key: str, measured_ms: float,
                            n: int = 1, **meta) -> None:
        rec = self.dispatch.get(key)
        if rec is None:
            rec = {"measured_ms": float(measured_ms), "n": int(n), **meta}
        else:
            tot = rec["n"] + n
            rec = dict(rec)
            rec["measured_ms"] = (rec["measured_ms"] * rec["n"]
                                  + measured_ms * n) / tot
            rec["n"] = tot
            rec.update(meta)
        self.dispatch[key] = rec

    # -- (de)serialization -------------------------------------------
    def _payload(self) -> Dict:
        return {
            "kind": TABLE_KIND,
            "version": self.version,
            "device_kind": self.device_kind,
            "compute_dtype": self.compute_dtype,
            "source": self.source,
            "spec": self.spec,
            "xla_temp_factor": self.xla_temp_factor,
            "step_correction": self.step_correction,
            "ops": self.ops,
            "dispatch": self.dispatch,
        }

    @property
    def digest(self) -> str:
        return content_digest(self._payload())

    def to_json(self) -> Dict:
        return {**self._payload(), "digest": self.digest}

    def save(self, path: str) -> str:
        """Atomic write (tmp + rename: a crashed harvest must not leave
        a truncated table at the final name).  Returns the digest."""
        d = self.to_json()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(d, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return d["digest"]

    @classmethod
    def from_json(cls, data: Dict) -> "CalibrationTable":
        errs = validate_table(data)
        if errs:
            raise ValueError("invalid calibration table: "
                             + "; ".join(errs[:5]))
        t = cls(device_kind=data["device_kind"],
                compute_dtype=data.get("compute_dtype", "bfloat16"),
                source=data.get("source", ""))
        t.version = data["version"]
        t.spec = dict(data.get("spec") or {})
        t.xla_temp_factor = data.get("xla_temp_factor")
        t.step_correction = (dict(data["step_correction"])
                             if data.get("step_correction") else None)
        t.ops = {k: dict(v) for k, v in data.get("ops", {}).items()}
        t.dispatch = {k: dict(v)
                      for k, v in data.get("dispatch", {}).items()}
        return t

    @classmethod
    def load(cls, path: str) -> "CalibrationTable":
        with open(path) as f:
            return cls.from_json(json.load(f))


def content_digest(payload: Dict) -> str:
    """Canonical content digest (sorted-key JSON, ``digest`` excluded):
    two tables with the same measurements have the same digest on any
    machine, so a search's ``describe()`` names exactly which calibration
    state produced it."""
    body = {k: v for k, v in payload.items() if k != "digest"}
    blob = json.dumps(body, sort_keys=True,
                      separators=(",", ":")).encode()
    return "sha256:" + hashlib.sha256(blob).hexdigest()[:16]


def _check_rec(rec, where: str, errs: List[str]) -> None:
    if rec is None:
        return
    if not isinstance(rec, dict):
        errs.append(f"{where}: not an object")
        return
    for f in ("analytic_ms", "measured_ms", "n"):
        v = rec.get(f)
        if not isinstance(v, (int, float)) or v != v or v < 0:
            errs.append(f"{where}.{f}: want a non-negative number, "
                        f"got {v!r}")


def validate_table(data: Dict) -> List[str]:
    """Schema errors for a calibration-table JSON (empty = valid).
    Digest mismatches are reported too — a hand-edited table must not
    silently masquerade as the one that was harvested."""
    errs: List[str] = []
    if not isinstance(data, dict):
        return ["top level: want an object"]
    if data.get("kind") != TABLE_KIND:
        errs.append(f"kind: want {TABLE_KIND!r}, got {data.get('kind')!r}")
    if not isinstance(data.get("version"), int):
        errs.append("version: want an int")
    elif data["version"] > SCHEMA_VERSION:
        errs.append(f"version {data['version']} is newer than this "
                    f"reader ({SCHEMA_VERSION})")
    if not isinstance(data.get("device_kind"), str):
        errs.append("device_kind: want a string")
    ops = data.get("ops", {})
    if not isinstance(ops, dict):
        errs.append("ops: want an object")
        ops = {}
    for key, entry in ops.items():
        if not isinstance(entry, dict):
            errs.append(f"ops[{key!r}]: not an object")
            continue
        if len(key.split("|")) != 4:
            errs.append(f"ops[{key!r}]: key is not "
                        "op-type|shape-bucket|dtype|pN")
        if entry.get("fwd") is None:
            errs.append(f"ops[{key!r}]: missing fwd record")
        _check_rec(entry.get("fwd"), f"ops[{key!r}].fwd", errs)
        _check_rec(entry.get("bwd"), f"ops[{key!r}].bwd", errs)
        feats = entry.get("features")
        if not isinstance(feats, dict):
            errs.append(f"ops[{key!r}].features: want an object")
    disp = data.get("dispatch", {})
    if not isinstance(disp, dict):
        errs.append("dispatch: want an object")
        disp = {}
    for key, rec in disp.items():
        if not isinstance(rec, dict) or not isinstance(
                rec.get("measured_ms"), (int, float)):
            errs.append(f"dispatch[{key!r}]: want "
                        "{{measured_ms: number, ...}}")
    spec = data.get("spec", {})
    if spec:
        known = {f.name for f in dataclasses.fields(DeviceSpec)}
        for k, v in spec.items():
            if k not in known:
                errs.append(f"spec.{k}: not a DeviceSpec field")
            elif not isinstance(v, (int, float)) or v != v \
                    or abs(v) == float("inf"):
                # calibrated_spec() float()s these — a non-numeric value
                # must fail --check, not crash lint/search downstream
                errs.append(f"spec.{k}: want a finite number, got {v!r}")
    xtf = data.get("xla_temp_factor")
    if xtf is not None and (not isinstance(xtf, (int, float))
                            or xtf != xtf or abs(xtf) == float("inf")
                            or xtf <= 0):
        errs.append(f"xla_temp_factor: want a positive finite number, "
                    f"got {xtf!r}")
    sc = data.get("step_correction")
    if sc is not None:
        if not isinstance(sc, dict):
            errs.append("step_correction: want an object or null")
        else:
            for f in ("alpha", "beta"):
                v = sc.get(f)
                if not isinstance(v, (int, float)) or v != v \
                        or abs(v) == float("inf"):
                    errs.append(f"step_correction.{f}: want a finite "
                                f"number, got {v!r}")
            if not isinstance(sc.get("n"), int) or sc.get("n", 0) < 2:
                errs.append("step_correction.n: want an int >= 2 "
                            "(a power law from one point is noise)")
    if "digest" in data:
        want = content_digest(data)
        if data["digest"] != want:
            errs.append(f"digest mismatch: file says {data['digest']}, "
                        f"content is {want}")
    else:
        errs.append("digest: missing")
    return errs


def validate_file(path: str) -> List[str]:
    """Validate a calibration table file (schema + digest)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return [f"cannot read: {e}"]
    kind = data.get("kind") if isinstance(data, dict) else None
    if kind == TABLE_KIND:
        return validate_table(data)
    return [f"unknown kind {kind!r} (want {TABLE_KIND!r})"]


def default_table() -> CalibrationTable:
    """The seed CalibrationTable: the round-5 TPU v5 lite measurements
    that previously lived as comments in ``ops/conv.py`` /
    ``ops/attention.py`` and BASELINE.md ("Cost-model calibration"),
    now data (``calibration_seed.json``).  These are the measurements
    the analytic model's ``backward_overhead`` / ``internal_io_bytes``
    corrections were derived from — the provenance record, and a usable
    starting table on v5e-class chips."""
    return CalibrationTable.load(_SEED_PATH)


def fit_step_correction(pairs: Sequence[Tuple[float, float]]
                        ) -> Optional[Dict]:
    """Dispatch-level correction: fit ``measured = e^alpha * sim^beta``
    (least squares in log space, the 2008.01040 posture) over per-model
    ``(simulated step ms, measured dispatch ms-per-step)`` pairs.

    A per-op table cannot see what happens BETWEEN ops: on a large
    graph XLA fuses elementwise chains into their producers (the fused
    step beats the sum of isolated-op timings), while on a tiny graph
    the per-dispatch overhead dominates (the fused step is slower than
    the op sum).  One sublinear power law captures both regimes;
    fitting it from the harvest's own dispatch measurements is exactly
    the "measure real dispatches, fit a correction" loop the ROADMAP
    asks for.  Returns None with fewer than two usable pairs (the fit
    would be exact and meaningless)."""
    pts = [(math.log(x), math.log(y)) for x, y in pairs
           if x > 0 and y > 0 and math.isfinite(x) and math.isfinite(y)]
    if len(pts) < 2:
        return None
    n = len(pts)
    mx = sum(p[0] for p in pts) / n
    my = sum(p[1] for p in pts) / n
    sxx = sum((p[0] - mx) ** 2 for p in pts)
    if sxx <= 0:
        return None
    beta = sum((p[0] - mx) * (p[1] - my) for p in pts) / sxx
    if beta <= 0:
        return None  # anti-monotone fit: dispatch data is degenerate
    return {"alpha": round(my - beta * mx, 6), "beta": round(beta, 6),
            "n": n}


def apply_step_correction(table: Optional[CalibrationTable],
                          sim_ms: float) -> float:
    """Map a simulated per-step time (ms) through the table's dispatch
    correction; identity when the table carries none.  This calibrates
    ABSOLUTE end-to-end predictions; the search objective never needs
    it — the power law is monotone, so op-level rankings are unchanged
    by construction."""
    sc = table.step_correction if table is not None else None
    if not sc or sim_ms <= 0 or not math.isfinite(sim_ms):
        return sim_ms
    return math.exp(sc["alpha"]) * sim_ms ** sc["beta"]


def calibrated_spec(table: Optional[CalibrationTable],
                    base: Optional[DeviceSpec] = None) -> DeviceSpec:
    """Apply a table's measured DeviceSpec overrides (effective
    bandwidths/latencies) over ``base`` (default: the auto-selected
    generation spec).  Rebuilding the Simulator/verifier from this spec
    threads comm calibration through ``transfer_time``/``allreduce_time``
    — Python AND native engine, which both read the spec's numbers —
    and through the FF108 HBM budget."""
    spec = base if base is not None else spec_for_device()
    if table is None or not table.spec:
        return spec
    return dataclasses.replace(spec, **{k: float(v)
                                        for k, v in table.spec.items()})


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

class CostEstimator:
    """Pluggable per-op time model for the Simulator: ``op_time`` has
    the same contract as ``cost_model.op_compute_time`` (seconds for ONE
    partition of ``op`` under ``dims``).  ``Simulator(estimator=None)``
    — the default — never consults one, so uncalibrated runs are
    bit-identical to the raw analytic path."""

    name = "base"

    def op_time(self, op, dims, spec: DeviceSpec, dtype_bytes: int = 2,
                backward: bool = False, flash_attention=None,
                compute_dtype: str = "bfloat16",
                precision: str = "") -> float:
        raise NotImplementedError

    def describe(self) -> Dict[str, Optional[str]]:
        return {"estimator": self.name, "calibration_digest": None}


class AnalyticEstimator(CostEstimator):
    """The identity estimator: exactly ``op_compute_time``."""

    name = "analytic"

    def op_time(self, op, dims, spec, dtype_bytes=2, backward=False,
                flash_attention=None, compute_dtype="bfloat16",
                precision=""):
        return op_compute_time(op, dims, spec, dtype_bytes, backward,
                               flash_attention=flash_attention,
                               precision=precision)


class TableEstimator(AnalyticEstimator):
    """Analytic time × the measured/analytic ratio of the nearest table
    entry.  Lookup tiers (first hit wins, deterministic):

    1. exact key (op-type × shape-bucket × dtype × partition-degree);
    2. same op-type + dtype + degree, nearest output volume;
    3. same op-type + dtype, nearest output volume (any degree);
    4. same op-type, nearest output volume (any dtype);
    5. no entry — scale 1.0 (falls back to pure analytic).

    A missing backward record borrows the entry's forward scale (the
    systematic analytic error is usually shared); scales are clamped to
    a sane band so one corrupted sample cannot turn the objective into
    noise."""

    name = "table"
    SCALE_MIN, SCALE_MAX = 1e-4, 1e6

    def __init__(self, table: CalibrationTable):
        self.table = table
        # tiered indexes: key parts -> [(log2 out_volume, fwd, bwd)]
        self._exact: Dict[str, Tuple[float, float]] = {}
        by_tdp: Dict[Tuple[str, str, str], List] = {}
        by_td: Dict[Tuple[str, str], List] = {}
        by_t: Dict[str, List] = {}
        for key, entry in sorted(table.ops.items()):
            op_type, _bucket, dtype, deg = key.split("|")
            fwd, bwd = self._entry_scales(entry)
            if fwd is None:
                continue
            self._exact[key] = (fwd, bwd)
            vol = float((entry.get("features") or {}).get(
                "out_volume", 0.0)) or 1.0
            row = (math.log2(max(1.0, vol)), fwd, bwd)
            by_tdp.setdefault((op_type, dtype, deg), []).append(row)
            by_td.setdefault((op_type, dtype), []).append(row)
            by_t.setdefault(op_type, []).append(row)
        self._tiers = (by_tdp, by_td, by_t)

    @classmethod
    def _entry_scales(cls, entry: Dict
                      ) -> Tuple[Optional[float], Optional[float]]:
        def ratio(rec):
            if not rec or rec.get("analytic_ms", 0) <= 0:
                return None
            m = rec.get("measured_ms")
            if m is None or m != m or m <= 0:
                return None
            return min(cls.SCALE_MAX,
                       max(cls.SCALE_MIN, m / rec["analytic_ms"]))
        fwd = ratio(entry.get("fwd"))
        bwd = ratio(entry.get("bwd"))
        if bwd is None:
            bwd = fwd
        return fwd, bwd

    def _scale(self, op, dims, backward: bool, dtype: str) -> float:
        key = op_key(op, dims, dtype)
        hit = self._exact.get(key)
        if hit is None:
            op_type, _b, dt, deg = key.split("|")
            lv = math.log2(max(1.0, float(op.outputs[0].volume)))
            by_tdp, by_td, by_t = self._tiers
            for rows in (by_tdp.get((op_type, dt, deg)),
                         by_td.get((op_type, dt)), by_t.get(op_type)):
                if rows:
                    hit = min(rows, key=lambda r: (abs(r[0] - lv), r[0]))[1:]
                    break
        if hit is None:
            return 1.0
        return hit[1] if backward else hit[0]

    def op_time(self, op, dims, spec, dtype_bytes=2, backward=False,
                flash_attention=None, compute_dtype="bfloat16",
                precision=""):
        # The table is dtype-keyed (2008.01040's feature scheme): a
        # per-op precision override reaches the lookup through
        # ``compute_dtype`` (the simulator resolves the override's
        # dtype NAME; ``dtype_bytes`` arrives as the SESSION width and
        # the byte effect is applied here).  The analytic base
        # deliberately takes NO precision rate factor — a dtype-keyed
        # entry's measured/analytic ratio already embodies that dtype's
        # rate physics (the harvest computed its analytic denominator
        # without the factor), so charging it in the base too would
        # double-count the f32 MXU penalty on exact-tier hits.
        from .cost_model import precision_dtype_bytes
        base = op_compute_time(op, dims, spec,
                               precision_dtype_bytes(precision,
                                                     dtype_bytes),
                               backward,
                               flash_attention=flash_attention)
        return base * self._scale(op, dims, backward, compute_dtype)

    def describe(self):
        return {"estimator": self.name,
                "calibration_digest": self.table.digest}


class RidgeEstimator(CostEstimator):
    """Learned estimator: ridge regression over op features in log space
    (the linear baseline of 2008.01040's learned TPU performance model),
    fit from the table's entries at construction.  Features: log1p of
    per-partition FLOPs / elements in / elements out / weight elements /
    partition degree, plus fan-in/out.  Separate fwd and bwd fits; with
    fewer than ``MIN_SAMPLES`` measured entries the direction falls back
    to the analytic roofline (a regression on 2 points is noise)."""

    name = "ridge"
    MIN_SAMPLES = 3
    LAMBDA = 1e-3

    def __init__(self, table: CalibrationTable):
        self.table = table
        self._w_fwd = self._fit(table, backward=False)
        self._w_bwd = self._fit(table, backward=True)

    # feature map: raw table features -> design row
    @staticmethod
    def _phi(feats: Dict[str, float]) -> List[float]:
        nparts = max(1.0, float(feats.get("nparts", 1.0)))
        lp = lambda v: math.log1p(max(0.0, float(v)) / nparts)  # noqa: E731
        return [1.0,
                lp(feats.get("flops", 0.0)),
                lp(feats.get("in_elems", 0.0)),
                lp(feats.get("out_elems", 0.0)),
                lp(feats.get("weight_elems", 0.0)),
                math.log2(nparts),
                float(feats.get("fan_in", 1.0)),
                float(feats.get("fan_out", 1.0))]

    @classmethod
    def _fit(cls, table: CalibrationTable, backward: bool):
        import numpy as np
        rows, ys = [], []
        for entry in table.ops.values():
            rec = entry.get("bwd" if backward else "fwd")
            feats = entry.get("features")
            if not rec or not feats:
                continue
            m = rec.get("measured_ms")
            if m is None or m != m or m <= 0:
                continue
            rows.append(cls._phi(feats))
            ys.append(math.log(m))
        if len(rows) < cls.MIN_SAMPLES:
            return None
        X = np.asarray(rows, dtype=np.float64)
        y = np.asarray(ys, dtype=np.float64)
        a = X.T @ X + cls.LAMBDA * np.eye(X.shape[1])
        return np.linalg.solve(a, X.T @ y)

    def op_time(self, op, dims, spec, dtype_bytes=2, backward=False,
                flash_attention=None, compute_dtype="bfloat16",
                precision=""):
        w = self._w_bwd if backward else self._w_fwd
        if w is None:
            return op_compute_time(op, dims, spec, dtype_bytes, backward,
                                   flash_attention=flash_attention,
                                   precision=precision)
        import numpy as np
        phi = np.asarray(self._phi(op_features(op, dims)))
        t = float(math.exp(float(phi @ w))) * 1e-3  # ms -> s
        if precision:
            # the feature vector carries no dtype (2008.01040's set is
            # dtype-free; the table KEY holds it) — without a correction
            # every precision flip would cost delta == 0 and Metropolis
            # would accept arbitrary pins the objective never evaluated.
            # Thread the dtype physics through the ANALYTIC ratio of the
            # pinned vs session-dtype rooflines (bytes + MXU rate); ""
            # skips this branch, keeping the uncalibrated/unpinned path
            # bit-identical.
            pinned = op_compute_time(op, dims, spec, dtype_bytes,
                                     backward,
                                     flash_attention=flash_attention,
                                     precision=precision)
            session = op_compute_time(op, dims, spec, dtype_bytes,
                                      backward,
                                      flash_attention=flash_attention)
            if session > 0:
                t *= pinned / session
        return t

    def describe(self):
        return {"estimator": self.name,
                "calibration_digest": self.table.digest}


ESTIMATORS = ("analytic", "table", "ridge")


def make_estimator(name: str, table: Optional[CalibrationTable] = None
                   ) -> CostEstimator:
    if name == "analytic":
        return AnalyticEstimator()
    if table is None:
        raise ValueError(f"estimator {name!r} needs a calibration table "
                         f"(FFConfig.calibration_file / --calibration)")
    if name == "table":
        return TableEstimator(table)
    if name == "ridge":
        return RidgeEstimator(table)
    raise ValueError(f"unknown cost estimator {name!r} "
                     f"(have {', '.join(ESTIMATORS)})")


def estimator_from_config(cfg) -> Tuple[Optional[CostEstimator],
                                        Optional[CalibrationTable]]:
    """(estimator, table) for ``cfg.cost_estimator`` /
    ``cfg.calibration_file``.  The bit-identical contract: with no
    calibration configured this returns ``(None, None)`` and the caller
    passes ``estimator=None`` — the Simulator then never touches this
    module.  ``"auto"`` resolves to ``"table"`` when a file is set,
    ``"analytic"`` otherwise."""
    path = getattr(cfg, "calibration_file", "") or ""
    name = getattr(cfg, "cost_estimator", "auto") or "auto"
    if name == "auto":
        name = "table" if path else "analytic"
    try:
        table = CalibrationTable.load(path) if path else None
    except (OSError, ValueError) as e:
        raise ValueError(
            f"cannot load calibration table {path!r} "
            f"(--calibration / FFConfig.calibration_file): {e}") from e
    if name == "analytic":
        # an analytic run ignores the table for op times; return it so
        # callers can still record the digest they ran against
        return None, table
    return make_estimator(name, table), table


# ---------------------------------------------------------------------------
# harvesting
# ---------------------------------------------------------------------------

def _dtype_bytes(dtype: str) -> int:
    return 2 if "16" in dtype else 4


def _profile_best(op, samples: int = 2, **kw) -> Dict[str, float]:
    """Best-of-N ``profile_op`` (per direction): wall-clock noise only
    ever INFLATES a sample (bench.py's min-of-legs philosophy).  NaNs
    pass through (int-only ops)."""
    from ..profiling import profile_op
    best = {"fwd_ms": float("nan"), "bwd_ms": float("nan")}
    for _ in range(max(1, samples)):
        r = profile_op(op, **kw)
        for k in best:
            v = r[k]
            if v == v and not (best[k] == best[k] and best[k] <= v):
                best[k] = v
    return best


def harvest_ops(table: CalibrationTable, layers, *,
                compute_dtype: str = "bfloat16", iters: int = 4,
                warmup: int = 1, degrees: Sequence[int] = (1,),
                flash_attention=None, conv_layout: str = "auto",
                spec: Optional[DeviceSpec] = None, samples: int = 2,
                verbose: bool = False) -> int:
    """Microbench every op of ``layers`` on the attached device
    (``profiling.profile_op`` — the measure-mode timing path, best of
    ``samples`` runs per direction) at each partition degree in
    ``degrees`` (n-axis splits via ``Op.sub_problem``), and merge
    (analytic, measured) sample pairs into ``table``.  Identical
    (key, sub-shape) combinations are measured once.  Returns the
    number of new measurements."""
    from ..op import resolve_conv_layout
    spec = spec if spec is not None else spec_for_device()
    layout = resolve_conv_layout(conv_layout, list(layers))
    dtype_bytes = _dtype_bytes(compute_dtype)
    seen = set()
    n_new = 0
    for op in layers:
        nd = op.outputs[0].num_dims
        for deg in degrees:
            dims = (int(deg),) + (1,) * (nd - 1)
            in_shapes = weight_shapes = None
            if deg > 1:
                try:
                    in_shapes, weight_shapes = op.sub_problem(dims)
                except (AssertionError, ValueError):
                    continue  # indivisible at this degree
            key = op_key(op, dims, compute_dtype)
            dedupe = (key, tuple(map(tuple, in_shapes or ())),
                      tuple(sorted((weight_shapes or {}).items())))
            if dedupe in seen:
                continue
            seen.add(dedupe)
            try:
                r = _profile_best(op, samples=samples,
                                  compute_dtype=compute_dtype,
                                  warmup=warmup, iters=iters,
                                  flash_attention=flash_attention,
                                  input_shapes=in_shapes,
                                  weight_shapes=weight_shapes,
                                  conv_layout=layout)
            except Exception as e:  # noqa: BLE001 — one unprofilable op
                # must not lose the whole harvest
                if verbose:
                    print(f"# calibrate: {op.name} p{deg} failed: "
                          f"{type(e).__name__}: {e}", flush=True)
                continue
            fwd_ms, bwd_ms = r["fwd_ms"], r["bwd_ms"]
            if fwd_ms != fwd_ms:  # NaN: int-only op, nothing to time
                continue
            ana_f = op_compute_time(op, dims, spec, dtype_bytes, False,
                                    flash_attention=flash_attention) * 1e3
            ana_b = op_compute_time(op, dims, spec, dtype_bytes, True,
                                    flash_attention=flash_attention) * 1e3
            table.add_op_sample(
                key, op_features(op, dims), ana_f, fwd_ms,
                ana_b, bwd_ms if bwd_ms == bwd_ms else None)
            n_new += 1
            if verbose:
                print(f"# calibrate[{n_new}] {op.name} p{deg}: "
                      f"fwd {ana_f:.3f}->{fwd_ms:.3f} ms  "
                      f"bwd {ana_b:.3f}->{bwd_ms:.3f} ms", flush=True)
    return n_new


def harvest_train_dispatch(table: CalibrationTable, name: str, model,
                           x, y, *, epochs: int = 2) -> Optional[float]:
    """Harvest per-dispatch wall time from the real
    ``StepTraceAnnotation``-wrapped fit() loop: run one warm epoch (pays
    the compile), then ``epochs`` timed ones, and record the mean
    ``dispatch_ms`` from the epoch events into
    ``table.dispatch["train|<name>|k<K>|b<batch>"]``.  Returns the mean
    measured ms per dispatch (None when no event carried one)."""
    from ..fflogger import capture_events
    model.fit(x, y, epochs=1, verbose=False)  # warm
    with capture_events("ff") as events:
        model.fit(x, y, epochs=epochs, verbose=False)
    ms = [e["dispatch_ms"] for e in events
          if e.get("event") == "epoch" and "dispatch_ms" in e]
    if not ms:
        return None
    k = int(getattr(model.config, "steps_per_dispatch", 1) or 1)
    mean_ms = sum(ms) / len(ms)
    table.add_dispatch_sample(
        f"train|{name}|k{k}|b{model.config.batch_size}", mean_ms,
        n=len(ms), steps_per_dispatch=k,
        batch_size=model.config.batch_size)
    return mean_ms


def harvest_serve_dispatch(table: CalibrationTable, name: Optional[str],
                           snapshot: Dict) -> int:
    """Harvest the serving engine's per-shape-bucket dispatch medians
    (the ``per_bucket`` section ``ServingMetrics.snapshot`` reports)
    into ``table.dispatch["serve|<name>|bucket<b>"]`` entries.
    ``name=None`` keys on the snapshot's own ``model`` tag — the
    per-engine identity every serve_stats row now carries, so a fleet
    process harvesting N co-resident engines' snapshots can never
    attribute model B's dispatch times to model A.  Returns the number
    of buckets recorded."""
    if name is None:
        name = snapshot.get("model") or "default"
    per_bucket = snapshot.get("per_bucket") or {}
    n = 0
    for bucket, rec in sorted(per_bucket.items()):
        p50 = rec.get("dispatch_p50_ms")
        if p50 is None:
            continue
        table.add_dispatch_sample(
            f"serve|{name}|bucket{bucket}", float(p50),
            n=int(rec.get("dispatches", 1)), bucket=int(bucket))
        n += 1
    return n


# ---------------------------------------------------------------------------
# the model zoo (CPU-feasible scaled variants of the real builders)
# ---------------------------------------------------------------------------

def _zoo_transformer(batch: int, dtype: str = "float32"):
    from ..config import FFConfig
    from ..models.transformer import build_transformer
    cfg = FFConfig(batch_size=batch, compute_dtype=dtype)
    model, tokens, _ = build_transformer(
        cfg, num_layers=2, d_model=64, num_heads=4, d_ff=128,
        seq_len=32, vocab_size=1000)
    import numpy as np
    rng = np.random.default_rng(0)
    n = batch * 4
    x = rng.integers(0, 1000, (n, 32)).astype(np.int32)
    y = rng.integers(0, 2, (n, 1)).astype(np.int32)
    return model, x, y


def _zoo_dlrm(batch: int, dtype: str = "float32"):
    from ..config import FFConfig
    from ..models.dlrm import build_dlrm
    cfg = FFConfig(batch_size=batch, compute_dtype=dtype)
    model, _, _ = build_dlrm(
        cfg, embedding_size=(1000, 1000, 1000, 1000),
        sparse_feature_size=16, mlp_bot=(32, 64, 16),
        mlp_top=(80, 64, 1))
    import numpy as np
    rng = np.random.default_rng(0)
    n = batch * 4
    xs = [rng.integers(0, 1000, (n, 1)).astype(np.int32)
          for _ in range(4)]
    xs.append(rng.standard_normal((n, 32)).astype(np.float32))
    y = rng.standard_normal((n, 1)).astype(np.float32)
    return model, xs, y


def _zoo_inception(batch: int, dtype: str = "float32"):
    from ..config import FFConfig
    from ..models.inception import build_inception_v3
    cfg = FFConfig(batch_size=batch, compute_dtype=dtype)
    model, _, _ = build_inception_v3(cfg, image_size=75)
    import numpy as np
    rng = np.random.default_rng(0)
    n = batch * 2
    x = rng.standard_normal((n, 3, 75, 75)).astype(np.float32)
    y = rng.integers(0, 10, (n, 1)).astype(np.int32)
    return model, x, y


ZOO = {"transformer": _zoo_transformer, "dlrm": _zoo_dlrm,
       "inception": _zoo_inception}
_ZOO_BATCH = {"transformer": 8, "dlrm": 8, "inception": 2}


def device_kind() -> str:
    """The kind a harvested table is stamped with — the device jax
    reports, never a guess: a backend that does not come up raises."""
    import jax
    return jax.devices()[0].device_kind


# ---------------------------------------------------------------------------
# CLI: flexflow-tpu calibrate
# ---------------------------------------------------------------------------

def calibrate_main(argv=None) -> int:
    """``flexflow-tpu calibrate``: harvest a CalibrationTable from the
    model zoo on the attached device (per-op microbench + per-dispatch
    train timings, optionally serving per-bucket timings), or validate
    existing artifacts with ``--check`` (schema + digest, exit 1 on any
    error).  Replaces the retired ``scripts/calibrate_cost_model.py``
    hand-run report with a durable, consumable table."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="flexflow-tpu calibrate",
        description="harvest measured op/dispatch timings into a "
                    "CalibrationTable (docs/strategy_search.md "
                    "'Calibration'), or --check existing artifacts")
    ap.add_argument("--check", nargs="+", metavar="FILE", default=None,
                    help="validate calibration artifacts (schema + "
                         "digest) instead of harvesting")
    ap.add_argument("--out", default="calibration.json",
                    help="table output path")
    ap.add_argument("--models", default="transformer,dlrm,inception",
                    help=f"comma-separated zoo subset of: "
                         f"{','.join(sorted(ZOO))}")
    ap.add_argument("--iters", type=int, default=4,
                    help="profile_op timing iterations per op")
    ap.add_argument("--samples", type=int, default=2,
                    help="best-of-N profile runs per op/direction "
                         "(wall-clock noise only ever inflates a "
                         "sample)")
    ap.add_argument("--degrees", default="1,2",
                    help="partition degrees to microbench (n-axis "
                         "splits via Op.sub_problem)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--no-dispatch", action="store_true",
                    help="skip the per-dispatch fit() harvest")
    ap.add_argument("--serve", action="store_true",
                    help="also harvest serving per-bucket dispatch "
                         "timings (runs a short engine loop)")
    ap.add_argument("--from-seed", action="store_true",
                    help="start from the round-5 seed table instead of "
                         "an empty one")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.check is not None:
        rc = 0
        for path in args.check:
            errs = validate_file(path)
            if errs:
                rc = 1
                for e in errs:
                    print(f"{path}: {e}")
            else:
                with open(path) as f:
                    d = json.load(f)
                print(f"{path}: OK ({d.get('kind')}, "
                      f"digest {d.get('digest')})")
        return rc

    names = [m.strip() for m in args.models.split(",") if m.strip()]
    for m in names:
        if m not in ZOO:
            ap.error(f"unknown model {m!r}; choose from {sorted(ZOO)}")
    if args.serve and "transformer" not in names:
        ap.error("--serve harvests the serving path through the "
                 "transformer zoo model; add transformer to --models")
    degrees = tuple(int(d) for d in args.degrees.split(",") if d.strip())

    # warm-cache harvests, like every other harness (bench.py,
    # model_bottleneck.py)
    from ..compile_cache import enable as _enable_cache
    _enable_cache()

    table = default_table() if args.from_seed else CalibrationTable()
    seed_kind = table.device_kind if args.from_seed else ""
    table.device_kind = device_kind()
    if seed_kind not in ("", "unknown", table.device_kind):
        # running means merge seed rows with this machine's samples —
        # the stamped device_kind can only honestly name one of them
        print(f"# calibrate: WARNING --from-seed table was measured on "
              f"{seed_kind!r}; merging with {table.device_kind!r} "
              f"samples conflates devices in the saved table",
              flush=True)
    table.compute_dtype = args.dtype
    from ..fflogger import silenced
    n_ops = 0
    zoo_layers = {}
    for m in names:
        model, x, y = ZOO[m](_ZOO_BATCH[m], args.dtype)
        zoo_layers[m] = model.layers
        print(f"# calibrate: harvesting {m} "
              f"({len(model.layers)} ops)", flush=True)
        n_ops += harvest_ops(table, model.layers,
                             compute_dtype=args.dtype, iters=args.iters,
                             degrees=degrees, samples=args.samples,
                             verbose=args.verbose)
        if not args.no_dispatch:
            import flexflow_tpu as ff
            model.compile(ff.SGDOptimizer(lr=0.01))
            model.init_layers(seed=args.seed)
            with silenced("ff"):
                ms = harvest_train_dispatch(table, m, model, x, y)
            if ms is not None:
                print(f"# calibrate: {m} train dispatch "
                      f"{ms:.3f} ms", flush=True)
        if args.serve and m == "transformer":
            _harvest_serving_loop(table, m, model, x)
    table.step_correction = _fit_dispatch_correction(table, zoo_layers)
    digest = table.save(args.out)
    print(json.dumps({"wrote": args.out, "device_kind": table.device_kind,
                      "op_entries": len(table.ops),
                      "dispatch_entries": len(table.dispatch),
                      "step_correction": table.step_correction,
                      "measurements": n_ops, "digest": digest}))
    return 0


def _fit_dispatch_correction(table: CalibrationTable,
                             zoo_layers: Dict) -> Optional[Dict]:
    """Pair each harvested model's CALIBRATED simulated step time (the
    final table's TableEstimator over its graph) with its measured
    per-step dispatch time, and fit :func:`fit_step_correction` over the
    pairs.  Needs >= 2 models with both an op harvest and a dispatch
    entry."""
    if not table.ops or not table.dispatch:
        return None
    from .simulator import Simulator
    est = TableEstimator(table)
    pairs = []
    for m, layers in zoo_layers.items():
        rec = next((r for k, r in sorted(table.dispatch.items())
                    if k.startswith(f"train|{m}|")), None)
        if rec is None:
            continue
        dt = table.compute_dtype or "bfloat16"
        sim_ms = Simulator(num_devices=1, use_native=False, estimator=est,
                           dtype_bytes=_dtype_bytes(dt),
                           compute_dtype=dt).simulate(layers, {}) * 1e3
        k = max(1, int(rec.get("steps_per_dispatch", 1)))
        pairs.append((sim_ms, rec["measured_ms"] / k))
    return fit_step_correction(pairs)


def _harvest_serving_loop(table: CalibrationTable, name: str, model,
                          x) -> None:
    """Short serving run to feed per-bucket dispatch calibration."""
    from ..fflogger import silenced
    from ..serving.engine import ServingEngine
    if not model._compiled:  # --no-dispatch skipped the compile
        import flexflow_tpu as ff
        model.compile(ff.SGDOptimizer(lr=0.01))
        model.init_layers(seed=0)
    with silenced("ff", "serve"):
        engine = ServingEngine(model, max_batch=model.config.batch_size)
        with engine:
            futs = [engine.submit(*_rows(model, x, i)) for i in range(32)]
            for f in futs:
                f.result(timeout=120)
        n = harvest_serve_dispatch(table, name, engine.stats())
    print(f"# calibrate: {name} serving buckets harvested: {n}",
          flush=True)


def _rows(model, x, i):
    n_in = len(model.input_tensors)
    size = 1 + (i % 3)
    if n_in == 1:
        return (x[i: i + size],)
    return tuple(a[i: i + size] for a in x)
