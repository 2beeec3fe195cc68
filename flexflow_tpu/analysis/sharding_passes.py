"""Static sharding propagation — lint predicts exactly what the runtime
will do (ISSUE 9).

The runtime decides every tensor's placement in exactly two functions:
``parallel/sharding.output_spec`` (each op output, constrained during
tracing) and ``parallel/sharding.param_spec`` (each parameter, placed by
``FFModel.init_layers``/``_resolve_host_placements``).  This module runs
THOSE functions — not a reimplementation — over the whole graph against a
device-free :class:`~flexflow_tpu.parallel.mesh.AbstractMesh`, so the
static answers and the trace-time answers come from one code path
(``parallel.sharding.dim_entry`` on the shared ``_MeshAxes`` math) and
cannot diverge.  On top of the propagation:

* **FF120** — every replication fallback the runtime would record as
  FF106 is predicted here, with the same ``(name, dim, degree, axis,
  axis_size, reason)`` site payload (``predict_fallbacks``; the
  cross-validation tests compare the raw tuples bit-for-bit);
* **communication plan** — per-edge reshard/allgather volumes from
  producer/consumer spec mismatches plus per-parameter gradient
  allreduce volumes, the device-free report behind
  ``flexflow-tpu explain`` (``communication_plan`` /
  ``explain_report``), whose content digest is ``comm_plan_digest``;
* the liveness HBM timeline consumed here lives on the Simulator
  (``Simulator.memory_timeline`` — FF121, see
  ``analysis/strategy_passes.py``).

Everything here is device-free: a 64-device mesh spec is interpreted on
a CPU-only machine without allocating a single jax device.
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, List, Optional, Tuple

from ..config import ParallelConfig
from ..op import Op, pad_degrees, snap_degrees
from ..parallel.mesh import AbstractMesh, dim_axis_names
from .diagnostics import Diagnostic
from .verifier import fallback_site_diagnostics

MeshShape = Dict[str, int]

# a fallback site: the exact key the runtime recorder aggregates on
# (analysis.verifier.record_replicate_fallback)
Site = Tuple[str, int, int, Optional[str], int, str]


# ---------------------------------------------------------------------
# spec propagation + FF120 fallback prediction
# ---------------------------------------------------------------------

def propagate_specs(layers: List[Op],
                    strategies: Dict[str, ParallelConfig],
                    mesh) -> Tuple[Dict[int, tuple], Dict[Site, int]]:
    """Abstract interpretation of the runtime's placement pass: for a
    given (graph, strategy, mesh) return ``(specs, fallbacks)`` where
    ``specs`` maps tensor uid -> PartitionSpec entry tuple and
    ``fallbacks`` is the aggregated fallback-site dict the trace would
    record.

    Mirrors the runtime exactly:

    * op outputs: ``output_spec(t, pc, mesh)`` for every output of every
      op with a resolved config (``FFModel._run_ops`` constrains exactly
      those) — configless outputs get the replicate-by-default spec the
      same function computes, recording nothing (as at trace time);
    * parameters: ``param_spec(w, pc, mesh)`` once per unique Parameter
      with its FIRST owning op's config (``FFModel._placed_param``'s
      lookup order);
    * nothing is recorded on a single-device mesh — the runtime only
      constrains/places under a distributed mesh.
    """
    from ..parallel.sharding import output_spec, param_spec

    fallbacks: Dict[Site, int] = {}

    def collect(name, dim, degree, axis, axis_size, reason):
        key = (name, dim, degree, axis, axis_size, reason)
        fallbacks[key] = fallbacks.get(key, 0) + 1

    distributed = mesh.is_distributed
    specs: Dict[int, tuple] = {}
    seen_params = set()
    for op in layers:
        pc = strategies.get(op.name)
        for t in op.outputs:
            if pc is not None and distributed:
                spec = output_spec(t, pc, mesh, on_fallback=collect)
            else:
                spec = output_spec(t, None, mesh)
            specs[t.uid] = tuple(spec)
        if not distributed:
            continue
        for w in op.weights:
            if w.uid in seen_params:
                continue  # shared weight: first owner's config governs
            seen_params.add(w.uid)
            param_spec(w, pc, mesh, on_fallback=collect)
    return specs, fallbacks


def predict_fallbacks(layers: List[Op],
                      strategies: Dict[str, ParallelConfig],
                      mesh) -> Dict[Site, int]:
    """The FF120 site set: every replicate fallback the runtime would
    record (FF106) for this (graph, strategy, mesh), as raw site
    tuples.  ``set(predict_fallbacks(...))`` equals the runtime's
    recorded site set exactly (tests/test_sharding_passes.py pins it on
    the zoo models and 200 random strategies)."""
    _, fallbacks = propagate_specs(layers, strategies, mesh)
    return fallbacks


def fallback_prediction_diagnostics(layers: List[Op],
                                    strategies: Dict[str, ParallelConfig],
                                    mesh_shape: MeshShape,
                                    num_devices: int) -> List[Diagnostic]:
    """FF120 — the verifier pass: statically predicted replicate
    fallbacks, one diagnostic per site with the same payload the
    runtime's FF106 would carry."""
    try:
        mesh = AbstractMesh(mesh_shape, num_devices=max(
            num_devices, 1))
    except ValueError:
        # machine smaller than the mesh: FF112 already reports it; the
        # fallback prediction still runs against the mesh itself
        mesh = AbstractMesh(mesh_shape)
    sites = predict_fallbacks(layers, strategies, mesh)
    return fallback_site_diagnostics(sites, code="FF120")


# ---------------------------------------------------------------------
# static communication plan
# ---------------------------------------------------------------------

def _edge_kind(pdims: tuple, cdims: tuple) -> str:
    """Classify a producer/consumer partition seam: ``allgather`` when
    the consumer reads at coarser (or equal) degrees everywhere —
    devices gather shards they do not hold; ``slice`` when strictly
    finer everywhere — a local dynamic-slice, no collective (the
    prefix-aligned sub-axis subsets of ``_MeshAxes`` make the finer
    shard a subset of the held one); ``reshard`` for mixed seams
    (GSPMD lowers an all-to-all-class exchange)."""
    if all(c <= p for c, p in zip(cdims, pdims)):
        return "allgather"
    if all(c >= p for c, p in zip(cdims, pdims)):
        return "slice"
    return "reshard"


def communication_plan(layers: List[Op],
                       strategies: Dict[str, ParallelConfig],
                       mesh, dtype_bytes: int = 2,
                       sparse_tables=frozenset()) -> Dict:
    """The per-step collective traffic a strategy implies, derived
    statically from spec mismatches — no devices, no tracing.

    * **edges**: for every producer->consumer edge whose partitionings
      disagree (the same snap/projection rule the simulator's edge
      construction and the FF109 pass use), one row with the seam kind
      (`allgather`/`reshard`/`slice`), the full-tensor bytes moved per
      step (the FF109 accounting — an upper bound; `slice` seams move
      nothing), and the per-step collective count (forward + the
      mirrored backward gradient exchange);
    * **weight_sync**: per trainable parameter, the gradient allreduce
      the executor runs every step — bytes and replica-group size
      mirror ``Simulator._op_plan``'s costing branches (c-sharded
      weights move 1/c of the bytes across the non-c replica group;
      replicated weights allreduce across every degree; sparse-update
      tables exchange only the touched row gradients).

    Returns a JSON-ready dict; :func:`comm_plan_digest` stamps it.
    """
    from ..ops.linear import host_placed

    num_devices = mesh.num_devices
    owner = {t.uid: op for op in layers for t in op.outputs}

    def dims_for(op: Op) -> tuple:
        pc = strategies.get(op.name)
        out = op.outputs[0]
        if pc is None:
            return tuple(ParallelConfig.data_parallel(
                min(max(1, num_devices), out.shape[0]), out.num_dims).dims)
        return pad_degrees(pc.dims, out.num_dims)

    edges: List[Dict] = []
    for op in layers:
        cdims = dims_for(op)
        for t_in in op.inputs:
            prod = owner.get(t_in.uid)
            if prod is None or prod.outputs[0].uid != t_in.uid:
                continue  # secondary outputs: projection is op-specific
            pdims = snap_degrees(
                pad_degrees(dims_for(prod), t_in.num_dims), t_in.shape)
            in_dims = snap_degrees(
                pad_degrees(cdims, t_in.num_dims), t_in.shape)
            if tuple(pdims) == tuple(in_dims):
                continue
            kind = _edge_kind(tuple(pdims), tuple(in_dims))
            nbytes = (0 if kind == "slice"
                      else t_in.volume * dtype_bytes)
            edges.append({
                "src": prod.name, "dst": op.name,
                "tensor": t_in.name, "kind": kind,
                "producer_dims": list(pdims),
                "consumer_dims": list(in_dims),
                "bytes_per_step": int(nbytes),
                "collectives_per_step": 0 if kind == "slice" else 2,
            })

    weight_sync: List[Dict] = []
    for op in layers:
        if not op.weights:
            continue
        pc = strategies.get(op.name)
        out = op.outputs[0]
        dims = dims_for(op)
        axes = dim_axis_names(out.num_dims)
        # mirror Simulator._op_plan: host-placed candidates run the
        # dense gather path, so no sparse row-grad discount
        sparse = frozenset() if host_placed(pc) else frozenset(sparse_tables)
        c_deg, repl = 1, 1
        for deg, ax in zip(dims, axes):
            if ax == "c":
                c_deg *= deg
            else:
                repl *= deg
        for w in op.own_weights():     # a shared one syncs once
            if not w.trainable:
                continue
            wb = w.volume * 4
            if w.name in sparse:
                wb = op.inputs[0].volume * w.shape[-1] * 4
            if (w.sharded_dim is not None and c_deg > 1
                    and w.shape[w.sharded_dim] % c_deg == 0):
                nbytes, group = wb // c_deg, min(repl, num_devices)
            else:
                nbytes, group = wb, min(repl * c_deg, num_devices)
            if group <= 1 or nbytes <= 0:
                continue  # no replicas: nothing to reduce
            weight_sync.append({
                "op": op.name, "param": w.name, "kind": "allreduce",
                "bytes_per_step": int(nbytes), "replicas": int(group),
                "sparse_rows_only": w.name in sparse,
            })

    totals = {
        "edge_bytes_per_step": sum(e["bytes_per_step"] for e in edges),
        "allreduce_bytes_per_step": sum(w["bytes_per_step"]
                                        for w in weight_sync),
        "collectives_per_step": (
            sum(e["collectives_per_step"] for e in edges)
            + len(weight_sync)),
        "edges": len(edges),
        "allreduces": len(weight_sync),
    }
    edges.sort(key=lambda e: (-e["bytes_per_step"], e["src"], e["dst"]))
    weight_sync.sort(key=lambda w: (-w["bytes_per_step"], w["param"]))
    return {"edges": edges, "weight_sync": weight_sync, "totals": totals}


def comm_plan_digest(plan: Dict) -> str:
    """Stable content digest of a communication plan (sorted-key JSON,
    sha256, 16 hex chars) — what an ``explain`` report carries, so two
    reports of different sharding plans are never read as one."""
    blob = json.dumps(plan, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------
# the `explain` report
# ---------------------------------------------------------------------

def explain_report(model_name: str, layers: List[Op],
                   strategies: Optional[Dict[str, ParallelConfig]],
                   mesh_shape: Optional[MeshShape] = None,
                   num_devices: Optional[int] = None,
                   dtype_bytes: int = 2, spec=None,
                   opt_slot_bytes: int = 4,
                   sparse_tables=frozenset(),
                   serve_slots: int = 0,
                   serve_seq: int = 0,
                   serve_kv_page: int = 0,
                   serve_kv_pages: int = 0,
                   serve_prefill_chunk: int = 0) -> Dict:
    """The full device-free ``flexflow-tpu explain`` payload: propagated
    sharding summary, predicted FF120 fallbacks, the communication plan
    (+ digest), and the liveness HBM timeline.  ``mesh_shape`` defaults
    to the same static inference lint runs
    (``strategy_passes.infer_mesh_shape``).  ``serve_slots``/
    ``serve_seq`` > 0 size a token-generation deployment: the KV cache
    (analysis.kv_memory — the engine's own accounting) rides in the
    memory timeline's resident state and a ``kv_cache`` section is
    added."""
    from ..search.cost_model import spec_for_device
    from ..search.simulator import Simulator
    from .strategy_passes import infer_mesh_shape

    strategies = strategies or {}
    if mesh_shape is None:
        mesh_shape, _over = infer_mesh_shape(
            strategies, layers, num_devices or 10 ** 9)
    mesh_shape = {k: int(v) for k, v in mesh_shape.items() if int(v) > 1} \
        or {"n": 1}
    notes: List[str] = []
    try:
        # num_devices None -> the mesh product (the documented
        # --devices default), never a false machine-too-small note
        mesh = AbstractMesh(mesh_shape, num_devices=num_devices)
    except ValueError:
        # the machine is SMALLER than the mesh: still explain the plan
        # (the report is device-free), but say so instead of silently
        # overriding the caller's machine size — lint gates the same
        # condition as FF112
        mesh = AbstractMesh(mesh_shape)
        notes.append(
            f"requested machine of {num_devices} device(s) is smaller "
            f"than the mesh product {mesh.num_devices}; explaining the "
            f"mesh itself (flexflow-tpu lint reports this as FF112)")
    specs, fallbacks = propagate_specs(layers, strategies, mesh)
    plan = communication_plan(layers, strategies, mesh,
                              dtype_bytes=dtype_bytes,
                              sparse_tables=sparse_tables)
    spec = spec or spec_for_device()
    sim = Simulator(spec=spec, num_devices=mesh.num_devices,
                    use_native=False, dtype_bytes=dtype_bytes,
                    opt_slot_bytes=opt_slot_bytes,
                    sparse_tables=sparse_tables)
    kv_bytes = 0.0
    kv_section = None
    if serve_slots > 0 and serve_seq > 0:
        from .kv_memory import kv_page_plan
        kv_plan = kv_page_plan(layers, mesh_shape, serve_slots,
                               serve_seq, kv_dtype_bytes=dtype_bytes,
                               page_size=serve_kv_page,
                               num_pages=serve_kv_pages,
                               prefill_chunk=serve_prefill_chunk)
        kv_bytes = kv_plan["total_bytes"]
        kv_section = {"slots": int(serve_slots),
                      "max_seq": int(serve_seq),
                      "page_size": kv_plan["page_size"],
                      "num_pages": kv_plan["num_pages"],
                      "page_bytes": kv_plan["page_bytes"],
                      "pool_bytes": kv_plan["pool_bytes"],
                      "window_bytes": kv_plan["window_bytes"],
                      "window_rows": kv_plan["window_rows"],
                      "state_bytes": kv_plan["state_bytes"],
                      "bytes_per_device": kv_bytes}
    timeline = sim.memory_timeline(layers, strategies, mesh_shape,
                                   assume_remat=False,
                                   extra_state_bytes=kv_bytes)
    sharded = sum(1 for entries in specs.values()
                  if any(e not in (None, ()) for e in entries))
    return {
        **({"kv_cache": kv_section} if kv_section else {}),
        "report": "explain",
        "model": model_name,
        "mesh": dict(mesh.sizes),
        "num_devices": mesh.num_devices,
        "notes": notes,
        "ops": len(layers),
        "edges_propagated": len(specs),
        "tensors_sharded": sharded,
        "predicted_fallbacks": [
            {"op": name, "dim": dim, "degree": deg, "axis": axis,
             "axis_size": axis_size, "reason": reason}
            for (name, dim, deg, axis, axis_size, reason)
            in sorted(fallbacks)],
        "comm_plan": plan,
        "comm_plan_digest": comm_plan_digest(plan),
        "memory_timeline": {
            "state_bytes": timeline["state_bytes"],
            "peak_bytes": timeline["peak_bytes"],
            "peak_event": timeline["peak_event"],
            "peak_owners": timeline["peak_owners"],
            "events": len(timeline["events"]),
            "hbm_capacity_bytes": float(spec.hbm_capacity),
        },
    }


def render_explain_text(rep: Dict, top: int = 8) -> str:
    """Human rendering of an explain report."""
    lines = [
        f"explain: {rep['model']} on mesh "
        f"{ {k: v for k, v in rep['mesh'].items() if v > 1} or {'n': 1} } "
        f"({rep['num_devices']} device(s))",
        f"  {rep['ops']} ops, {rep['edges_propagated']} tensor specs "
        f"propagated, {rep['tensors_sharded']} sharded",
    ]
    for note in rep.get("notes", ()):
        lines.append(f"  NOTE: {note}")
    fb = rep["predicted_fallbacks"]
    if fb:
        lines.append(f"  predicted replicate fallbacks (FF120): {len(fb)}")
        for s in fb[:top]:
            lines.append(
                f"    {s['op']}: degree {s['degree']} on dim {s['dim']} "
                f"({s['reason']})")
    else:
        lines.append("  predicted replicate fallbacks (FF120): none — "
                     "the strategy executes as written")
    t = rep["comm_plan"]["totals"]
    lines.append(
        f"  comm plan [{rep['comm_plan_digest']}]: "
        f"{t['edges']} partition seam(s) "
        f"({t['edge_bytes_per_step'] / 1e6:.2f} MB/step), "
        f"{t['allreduces']} weight allreduce(s) "
        f"({t['allreduce_bytes_per_step'] / 1e6:.2f} MB/step), "
        f"{t['collectives_per_step']} collective(s)/step")
    for e in rep["comm_plan"]["edges"][:top]:
        lines.append(
            f"    {e['kind']:9s} {e['src']} -> {e['dst']}: "
            f"{e['bytes_per_step'] / 1e6:.2f} MB/step "
            f"(split {tuple(e['producer_dims'])} -> "
            f"{tuple(e['consumer_dims'])})")
    for w in rep["comm_plan"]["weight_sync"][:top]:
        lines.append(
            f"    allreduce {w['param']}: "
            f"{w['bytes_per_step'] / 1e6:.2f} MB/step "
            f"x{w['replicas']} replicas"
            + (" (sparse rows)" if w.get("sparse_rows_only") else ""))
    m = rep["memory_timeline"]
    kv = rep.get("kv_cache")
    if kv:
        lines.append(
            f"  KV cache: {kv['slots']} decode slot(s) x "
            f"{kv['max_seq']} positions = "
            f"{kv['bytes_per_device'] / 1e6:.2f} MB/device "
            f"({kv['num_pages']} pages of {kv['page_size']} tokens; "
            f"resident in the timeline below)")
    lines.append(
        f"  HBM timeline: state {m['state_bytes'] / 1e9:.3f} GB, "
        f"high-water {m['peak_bytes'] / 1e9:.3f} GB at "
        f"{m['peak_event']['phase']} {m['peak_event']['op']!r} "
        f"(budget {m['hbm_capacity_bytes'] / 1e9:.1f} GB)")
    for o in m["peak_owners"]:
        lines.append(f"    peak owner {o['op']}: "
                     f"{o['act_bytes'] / 1e6:.2f} MB resident")
    return "\n".join(lines)


# ---------------------------------------------------------------------
# schema validation (scripts/static_checks.sh gates the shipped .pb
# strategies' lint/explain JSON on these, like the calib artifacts)
# ---------------------------------------------------------------------

def validate_explain_json(obj) -> List[str]:
    """Schema check for an explain report; returns problem strings
    (empty = valid)."""
    probs: List[str] = []

    def want(cond, msg):
        if not cond:
            probs.append(msg)

    want(isinstance(obj, dict), "report must be an object")
    if not isinstance(obj, dict):
        return probs
    want(obj.get("report") == "explain", "report != 'explain'")
    for key, typ in (("model", str), ("mesh", dict), ("num_devices", int),
                     ("ops", int), ("predicted_fallbacks", list),
                     ("comm_plan", dict), ("comm_plan_digest", str),
                     ("memory_timeline", dict)):
        want(isinstance(obj.get(key), typ), f"{key}: want {typ.__name__}")
    want(isinstance(obj.get("notes", []), list), "notes: want a list")
    for s in obj.get("predicted_fallbacks", []) or []:
        want(isinstance(s, dict)
             and isinstance(s.get("op"), str)
             and isinstance(s.get("dim"), int)
             and isinstance(s.get("degree"), int)
             and isinstance(s.get("reason"), str),
             f"malformed fallback site {s!r}")
    plan = obj.get("comm_plan")
    if isinstance(plan, dict):
        want(isinstance(plan.get("edges"), list), "comm_plan.edges")
        want(isinstance(plan.get("weight_sync"), list),
             "comm_plan.weight_sync")
        totals = plan.get("totals")
        want(isinstance(totals, dict), "comm_plan.totals")
        for e in plan.get("edges", []) or []:
            want(isinstance(e, dict)
                 and e.get("kind") in ("allgather", "reshard", "slice")
                 and isinstance(e.get("bytes_per_step"), int),
                 f"malformed edge {e!r}")
        for w in plan.get("weight_sync", []) or []:
            want(isinstance(w, dict) and w.get("kind") == "allreduce"
                 and isinstance(w.get("bytes_per_step"), int)
                 and isinstance(w.get("replicas"), int),
                 f"malformed weight_sync {w!r}")
        if isinstance(plan, dict) and isinstance(
                obj.get("comm_plan_digest"), str):
            want(obj["comm_plan_digest"] == comm_plan_digest(plan),
                 "comm_plan_digest does not match the plan content")
    tl = obj.get("memory_timeline")
    if isinstance(tl, dict):
        for key in ("state_bytes", "peak_bytes", "hbm_capacity_bytes"):
            want(isinstance(tl.get(key), (int, float)),
                 f"memory_timeline.{key}")
        want(isinstance(tl.get("peak_owners"), list),
             "memory_timeline.peak_owners")
    return probs
