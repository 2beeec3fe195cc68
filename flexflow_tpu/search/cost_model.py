"""Analytic TPU cost model for the strategy simulator.

Replaces the reference's device model (``src/runtime/simulator.cu:27-29``:
inter-GPU 20 GB/s, inter-node 12/numNodes GB/s, GPU<->DRAM 16 GB/s) and its
on-hardware cuDNN microbenchmarks (conv_2d.cu:935-1037) with an MXU
roofline + ICI/DCN bandwidth table.  Default constants are TPU v5p per-chip
figures (scaling-book numbers); override via ``DeviceSpec`` for other
generations, or use measure mode (simulator.py) for on-hardware calibration
— the same two-tier design as the reference (analytic scripts/simulator.cc
vs measured simulator.cc:235-273).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

from ..op import Op, OpType


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Per-chip TPU capability model."""

    mxu_flops: float = 459e12        # bf16 FLOP/s (v5p)
    vpu_flops: float = 7e12          # elementwise FLOP/s
    hbm_bw: float = 2765e9           # bytes/s
    hbm_capacity: float = 95e9       # bytes per chip (v5p HBM)
    ici_bw: float = 90e9             # bytes/s per link direction
    dcn_bw: float = 25e9             # bytes/s per host (multi-slice)
    ici_latency: float = 1e-6        # s
    kernel_launch: float = 2e-6      # per-fused-region overhead (XLA amortizes)


# XLA's real buffer assignment exceeds the params+grads+slots+activations
# model: backward scratch and fusion temporaries measured 1.4-2.1x the
# analytic estimate on the bench chip (BASELINE.md "Memory-model
# validation", round-5 memory_analysis rows).  The HBM legality check
# multiplies the analytic peak by this calibrated factor so a strategy
# is only accepted when the COMPILER's footprint fits.
XLA_TEMP_FACTOR = 2.1

# Public spec-sheet figures per generation.
V5P_SPEC = DeviceSpec()
V5E_SPEC = DeviceSpec(mxu_flops=197e12, vpu_flops=4e12, hbm_bw=819e9,
                      hbm_capacity=16e9, ici_bw=45e9)
V6E_SPEC = DeviceSpec(mxu_flops=918e12, vpu_flops=9e12, hbm_bw=1640e9,
                      hbm_capacity=32e9, ici_bw=90e9)

_KIND_TO_SPEC = {
    "TPU v5 lite": V5E_SPEC, "TPU v5e": V5E_SPEC,
    "TPU v5": V5P_SPEC, "TPU v5p": V5P_SPEC,
    "TPU v6 lite": V6E_SPEC, "TPU v6e": V6E_SPEC,
}

DEFAULT_SPEC = V5P_SPEC


def spec_for_device(device_kind: str | None = None) -> DeviceSpec:
    """Pick the DeviceSpec matching the attached chip (the reference bakes
    one GPU fabric model into simulator.cu:27-29; we auto-select per
    generation).  On a TPU an unknown kind raises: pricing a chip nobody
    described as a v5p would steer the search by a wrong machine.  Off
    the TPU (the CPU test mesh, the device-free lint/explain tools)
    DEFAULT_SPEC stands in, so those stay deterministic.  An explicit
    ``device_kind`` is looked up as a TPU kind."""
    if device_kind is None:
        import jax
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return DEFAULT_SPEC
        device_kind = dev.device_kind
    if device_kind not in _KIND_TO_SPEC:
        raise ValueError(
            f"no DeviceSpec for device_kind {device_kind!r} (known: "
            f"{sorted(_KIND_TO_SPEC)}); add its published figures to "
            f"search/cost_model.py before searching or linting on it")
    return _KIND_TO_SPEC[device_kind]

# ops whose arithmetic runs on the VPU, not the MXU
_VPU_OPS = {
    OpType.ELEMENT_UNARY, OpType.ELEMENT_BINARY, OpType.SOFTMAX,
    OpType.BATCHNORM, OpType.LAYERNORM, OpType.RMSNORM, OpType.DROPOUT,
    OpType.POOL2D, OpType.EMBEDDING, OpType.CONCAT, OpType.SPLIT,
    OpType.FLAT, OpType.RESHAPE, OpType.TRANSPOSE, OpType.EXIT_GATE,
}


def precision_dtype_bytes(precision: str, default: int) -> int:
    """Activation byte width of one op under a strategy's precision
    token: ``""`` follows the session dtype (``default`` — the
    bit-identical path), ``"bf16"``/``"f32"`` force 2/4.  THE one
    precision→bytes rule shared by the time roofline, the FF108/FF121
    memory accounting and the SimSession's incremental cache."""
    if precision == "bf16":
        return 2
    if precision == "f32":
        return 4
    return default


# f32 matmuls run the MXU at half its bf16 rate (each f32 multiply
# occupies two bf16 passes through the systolic array); VPU ops are
# rate-flat across dtypes (their cost moves through the BYTES term).
# The rate factor is charged ONE-SIDED by design: only an EXPLICIT
# "f32" pin pays it, while the "" default keeps the session's legacy
# dtype-blind full rate — the bit-identity contract (default policy ==
# HEAD everywhere) forbids re-rating unpinned ops, so in an f32
# session a bf16 pin is credited its bytes but NOT the 2x MXU rate it
# would really gain.  The understatement is conservative (searched
# mixed strategies can only be better on silicon than simulated, never
# worse); the calibrated estimators recover the real differential
# through their dtype-keyed measurements.
_F32_MXU_SCALE = 0.5


def op_compute_time(op: Op, part_degrees: Tuple[int, ...],
                    spec: DeviceSpec = DEFAULT_SPEC,
                    dtype_bytes: int = 2, backward: bool = False,
                    flash_attention=None, precision: str = "") -> float:
    """Roofline time for ONE partition of ``op`` under the given degrees:
    max(compute, memory) + launch overhead.  Backward ~= 2x forward FLOPs
    (dgrad + wgrad), matching the reference's separate bwdData/bwdFilter
    measurement.

    ``precision`` is the op's strategy-level dtype override (ISSUE 14,
    ``ParallelConfig.precision``): ``"bf16"``/``"f32"`` charge the op's
    activation traffic at 2/4 bytes and run MXU ops at full/half rate;
    the default ``""`` leaves every term exactly as the caller's
    ``dtype_bytes`` implies — bit-identical to a build without the
    precision axis."""
    nparts = 1
    for d in part_degrees:
        nparts *= d
    flops = op.flops() / max(1, nparts)
    if backward:
        flops *= 2.0
    peak = spec.vpu_flops if op.op_type in _VPU_OPS else spec.mxu_flops
    peak *= op.mxu_efficiency()
    if precision == "f32" and op.op_type not in _VPU_OPS:
        peak *= _F32_MXU_SCALE
    dtype_bytes = precision_dtype_bytes(precision, dtype_bytes)
    io_bytes = 0
    for t in list(op.inputs) + list(op.outputs):
        io_bytes += t.volume * dtype_bytes
    io_bytes += sum(w.volume * 4 for w in op.weights)
    # intermediates the boundary tensors don't show (dense attention's
    # f32 score matrix, norm-stat passes) — see Op.internal_io_bytes
    io_bytes += op.internal_io_bytes(flash_attention=flash_attention)
    io_bytes /= max(1, nparts)
    if backward:
        io_bytes *= 2.0
    t = max(flops / peak, io_bytes / spec.hbm_bw)
    if backward:
        # calibrated lowering overhead (Op.backward_overhead): applied to
        # the whole backward roofline term, since the measured excess is
        # in the kernel the backward lowers TO (SelectAndScatter /
        # dilated dgrad), whichever side of the roofline binds
        t *= op.backward_overhead(part_degrees)
    return t + spec.kernel_launch


# Ops whose outputs XLA never materializes as standalone HBM buffers in a
# fused training step: pure layout views (reshape/transpose/flat/split)
# and unary epilogues that fuse into the adjacent matmul or conv kernel
# (dropout's mask is recomputed from the rng, not stored).  Counting them
# as resident is what inflated the round-3 high-water model several-fold
# on deep nets (VERDICT r3 weak #3).  ELEMENT_BINARY stays RESIDENT: a
# residual add's output is the trunk activation every downstream consumer
# retains for backward — excluding it would let truly-OOM strategies pass
# the legality check.
_UNMATERIALIZED_OPS = {
    OpType.RESHAPE, OpType.TRANSPOSE, OpType.FLAT, OpType.SPLIT,
    OpType.ELEMENT_UNARY, OpType.DROPOUT,
}


def op_memory_bytes(op: Op, part_degrees: Tuple[int, ...],
                    dtype_bytes: int = 2, opt_slot_bytes: int = 4,
                    axes: Tuple[str, ...] = (),
                    stack_degrees: Dict[str, int] | None = None,
                    remat: bool = False,
                    act_scale: float | None = None,
                    sparse_tables=frozenset()) -> float:
    """Per-chip resident bytes one op contributes to the training step's
    high-water mark (reference: the simulator allocates its scratch from
    real FB memory, simulator.cu:82-88, so unfittable strategies are
    unrunnable there; here the accounting is explicit):

    * parameters + their gradients (f32) + optimizer slots, sharded over
      the ``c`` (channel/TP) degrees when the weight declares a
      ``sharded_dim``, replicated otherwise;
    * expert-/stage-stacked weights (``shard_axis`` 'e'/'p') shard over
      their dedicated mesh axis at the size given in ``stack_degrees``
      ({"e": ..., "p": ...}); absent/1 means REPLICATED — the
      conservative truth on meshes that do not raise those axes (the
      SOAP search's candidate meshes pin e=p=1);
    * the op's output activations (retained for backward), divided over
      ALL partition degrees — EXCEPT view/fused ops whose outputs XLA
      never materializes (``_UNMATERIALIZED_OPS``).  Under ``remat``
      (sqrt(N)-segmented ``jax.checkpoint``, model.py ``_execute_remat``)
      the resident fraction is ``act_scale``: segment boundaries plus one
      recomputed segment interior, which the caller that knows the layer
      count sets to ``2/sqrt(N)`` (``Simulator.peak_memory_bytes``);
      standalone calls fall back to 0.5, the value of that expression at
      the ~17-op scale the constant was validated at (saved-residual
      measurement: boundaries alone are ~0.11x at N=17, plus one
      interior's recompute ~0.25x, model 0.49x — conservative).

    Delegates to :func:`op_memory_components` — ONE accounting shared
    with the liveness timeline (``Simulator.memory_timeline``), so the
    FF108 scalar bound and the FF121 interval analysis cannot drift.
    """
    state, act = op_memory_components(
        op, part_degrees, dtype_bytes=dtype_bytes,
        opt_slot_bytes=opt_slot_bytes, axes=axes,
        stack_degrees=stack_degrees, remat=remat, act_scale=act_scale,
        sparse_tables=sparse_tables)
    return state + act


def op_memory_components(op: Op, part_degrees: Tuple[int, ...],
                         dtype_bytes: int = 2, opt_slot_bytes: int = 4,
                         axes: Tuple[str, ...] = (),
                         stack_degrees: Dict[str, int] | None = None,
                         remat: bool = False,
                         act_scale: float | None = None,
                         sparse_tables=frozenset()) -> Tuple[float, float]:
    """The two liveness classes of :func:`op_memory_bytes`, separated for
    the interval analysis (``Simulator.memory_timeline``):

    * ``state_bytes`` — params + grads + optimizer slots: resident for
      the WHOLE training step (live range = the full interval; donation
      means the updated copy replaces, never doubles, them);
    * ``act_bytes`` — the op's retained output activations: live from
      the op's forward event until its own backward event completes
      (in reverse topological order an op's backward is the last use of
      its stored activation — every consumer's backward ran earlier).

    Same accounting, same arguments, same sharding rules as
    :func:`op_memory_bytes` — that function remains the one-shot sum
    (``state + act``) the FF108 legality bound and the search's inf
    gate are pinned to."""
    stack_degrees = stack_degrees or {}
    if act_scale is None:
        act_scale = 0.5 if remat else 1.0
    c_deg = 1
    for deg, ax in zip(part_degrees, axes):
        if ax == "c":
            c_deg *= deg
    nparts = 1
    for d in part_degrees:
        nparts *= d
    state = 0.0
    for w in op.own_weights():     # a shared parameter resides once
        if w.name in sparse_tables:
            # sparse-update table (FFModel._sparse_embedding_specs): no
            # table-shaped gradient ever materializes (row grads are
            # activation-sized) and plain SGD — the eligibility
            # condition — keeps no slots; only the params reside
            per_param = w.volume * 4.0
        else:
            per_param = w.volume * (4.0 * 2 + opt_slot_bytes)  # +grad+slots
        stack_ax = getattr(w, "shard_axis", "c")
        if stack_ax in ("e", "p") and w.sharded_dim is not None:
            deg = stack_degrees.get(stack_ax, 1)
            per_param /= max(1, min(w.shape[w.sharded_dim], deg))
        elif (w.sharded_dim is not None and c_deg > 1
                and w.shape[w.sharded_dim] % c_deg == 0):
            per_param /= c_deg
        state += per_param
    act = 0.0
    if op.op_type not in _UNMATERIALIZED_OPS:
        for t in op.outputs:
            act += act_scale * t.volume * dtype_bytes / max(1, nparts)
    return state, act


def transfer_time(nbytes: float, intra_slice: bool,
                  spec: DeviceSpec = DEFAULT_SPEC) -> float:
    """Point-to-point transfer cost (reference simulator.cc:200-233: 1 comm
    task intra-node, 3-hop chain inter-node; here: ICI hop vs DCN hop)."""
    if nbytes <= 0:
        return 0.0
    bw = spec.ici_bw if intra_slice else spec.dcn_bw
    return spec.ici_latency + nbytes / bw


def allreduce_time(nbytes: float, num_replicas: int,
                   spec: DeviceSpec = DEFAULT_SPEC,
                   members_per_slice: int = 0) -> float:
    """Ring-allreduce cost over ICI: 2*(k-1)/k * bytes / bw.  This replaces
    the reference's single-GPU replica-sum gather (optimizer_kernel.cu:168-179,
    costed as 2*weight_volume per extra replica in simulator.cc:358-408).

    ``members_per_slice`` — how many of the group's members share one ICI
    domain (0 = all of them).  A group spanning multiple slices runs the
    hierarchical form: reduce-scatter within each slice over ICI, a ring
    over the slow inter-slice fabric on the already-scattered 1/k1 shard,
    then an intra-slice all-gather.  This is the TPU equivalent of the
    reference's inter-node fabric term (simulator.cu:27-29: inter-node
    bandwidth 12/numNodes GB/s vs 20 GB/s intra)."""
    if num_replicas <= 1 or nbytes <= 0:
        return 0.0
    k1 = min(num_replicas, members_per_slice or num_replicas)
    k2 = -(-num_replicas // max(1, k1))  # slices spanned
    t = 0.0
    if k1 > 1:
        t += (spec.ici_latency * (k1 - 1)
              + 2.0 * (k1 - 1) / k1 * nbytes / spec.ici_bw)
    if k2 > 1:
        t += (spec.ici_latency * (k2 - 1)
              + 2.0 * (k2 - 1) / k2 * (nbytes / max(1, k1)) / spec.dcn_bw)
    return t
