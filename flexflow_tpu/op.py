"""Op — abstract operator base (reference ``include/model.h:190-230``).

A reference Op owns Legion task implementations (init/forward/backward) plus
partition builders and an on-GPU ``measure_compute_time`` hook.  The TPU-native
Op is much thinner by design:

* ``forward(params, inputs, ctx)`` is a *pure jax function*; backward comes
  from autodiff (``jax.grad``) instead of hand-written backward tasks, and
  gradient accumulation over replicas is XLA's psum instead of the enlarged
  grad-region trick (reference ``optimizer_kernel.cu:168-179``).
* partitioning is declarative: ``parallel_dims()`` names which output dims a
  strategy may split (the SOAP legality predicate, replacing the per-op
  asserts like conv_2d.cu:201's ``num_par_c==1``), and the resolved
  ParallelConfig turns into a ``jax.sharding`` PartitionSpec constraint rather
  than a Legion partition tree.
* ``flops()``/``bytes()`` feed the analytic simulator (replacing the
  on-hardware ``measure_compute_time`` as default; a measure mode still
  exists in flexflow_tpu/search/simulator.py).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from .config import ParallelConfig
from .tensor import Parameter, Tensor


class OpType(enum.Enum):
    CONV2D = "conv2d"
    POOL2D = "pool2d"
    LINEAR = "linear"
    EMBEDDING = "embedding"
    FLAT = "flat"
    SOFTMAX = "softmax"
    CONCAT = "concat"
    SPLIT = "split"
    RESHAPE = "reshape"
    TRANSPOSE = "transpose"
    DROPOUT = "dropout"
    BATCHNORM = "batchnorm"
    LAYERNORM = "layernorm"
    RMSNORM = "rmsnorm"
    ELEMENT_UNARY = "element_unary"
    ELEMENT_BINARY = "element_binary"
    MSELOSS = "mse_loss"
    ATTENTION = "attention"
    LSTM = "lstm"
    PIPELINE = "pipeline"
    MOE = "moe"
    EXIT_GATE = "exit_gate"
    INPUT = "input"


def resolve_conv_layout(value: str, layers=None) -> str:
    """Normalize + validate a conv_layout setting.  A typo must FAIL, not
    silently run NCHW — an A/B whose 'nhwc' arm silently benchmarks nchw
    records a bogus no-difference result.

    ``auto`` + a layer list consults the round-4/5 on-chip A/B
    (BASELINE.md): NHWC won only on Inception (+1.4 MFU pts), regressed
    ResNet-50 and was flat on AlexNet.  The cheap graph property that
    separates them is CONCAT-heaviness — inception blocks funnel every
    branch through channel concats, whose NCHW boundary transposes are
    the cost NHWC removes — so auto flips to NHWC on TPU when the graph
    has >= 2 concats among its convs, and stays NCHW otherwise
    (including every CPU-mesh test run, for determinism).  This puts the
    measured win in ``fit()`` for library users, not just the bench
    harness (VERDICT r4 weak #6/ask #7)."""
    v = (value or "auto").lower()
    if v not in ("nchw", "nhwc", "auto"):
        raise ValueError(
            f"conv_layout must be 'nchw', 'nhwc' or 'auto', got {value!r}")
    if v != "auto":
        return v
    if layers is None:
        return "nchw"
    try:
        import jax
        if jax.default_backend() != "tpu":
            return "nchw"
    except Exception:  # noqa: BLE001 - no backend: stay deterministic
        return "nchw"
    n_concat = sum(1 for op in layers
                   if op.op_type == OpType.CONCAT
                   and op.outputs[0].num_dims == 4)
    n_conv = sum(1 for op in layers if op.op_type == OpType.CONV2D)
    return "nhwc" if (n_concat >= 2 and n_conv > 0) else "nchw"


def pad_degrees(part_degrees, rank: int):
    """Output partition degrees padded/truncated to ``rank`` dims — the
    one shared idiom for aligning a strategy's degree tuple to a tensor's
    rank (graph simulator, memory model, and measure mode must agree)."""
    return tuple(part_degrees[:rank]) + \
        (1,) * max(0, rank - len(part_degrees))


def snap_degrees(dims, shape):
    """Replicate (degree 1) any dim a degree does not divide — the graph
    simulator's fallback for indivisible inputs (simulator.simulate_py)."""
    return tuple(d if d <= s and s % max(1, d) == 0 else 1
                 for d, s in zip(dims, shape))


@dataclasses.dataclass
class OpContext:
    """Per-trace execution context threaded through op forward functions."""

    training: bool = True
    rng: Optional[jax.Array] = None
    compute_dtype: str = "bfloat16"
    mesh: Optional[object] = None  # MachineMesh when compiled multi-chip
    # Pallas flash attention: None = auto (flash at s >= 1024 on TPU,
    # the measured v5e crossover — see FFConfig.flash_attention)
    flash_attention: Optional[bool] = None
    # internal conv/pool layout: "nchw" (reference parity, default) or
    # "nhwc" (channels-minor: TPU lane dimension; FFConfig.conv_layout).
    # Tensor METADATA stays NCHW either way — ops transpose at their own
    # boundaries, and XLA cancels the back-to-back pairs between
    # conv/pool neighbors.
    conv_layout: str = "nchw"
    # functional state updates: ops write {param_name: new_value} here for
    # non-trainable state (batchnorm running stats); the train step returns
    # them as part of the new params pytree
    updates: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    # auxiliary losses (e.g. MoE load balancing): {op_name: scalar}; the
    # train step adds their sum to the objective
    aux_losses: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    # sparse embedding updates: {embedding op name: pre-gathered rows}
    # injected by the train step so autodiff differentiates w.r.t. the
    # ROWS (n, [bag/s,] d) instead of the full table — see
    # FFConfig.sparse_embedding_updates
    embedding_rows: Optional[Dict[str, jax.Array]] = None


@dataclasses.dataclass(frozen=True)
class ServeStep:
    """Where one serving step stands — what :meth:`Op.serve_step` is told
    beside its inputs.  ``kind`` is one of

    * ``"chunk"``: prompt positions ``start .. start+B-1`` of ONE slot
      (inputs ``(1, B, ..)``, the first ``length`` rows real); ``table``
      is that slot's page-table row ``(pages_per_slot,)``, and the rows'
      write indices are computed in the program from it;
    * ``"token"``: one position of EVERY slot (inputs ``(slots, 1, ..)``)
      at ``pos`` (slots,);
    * ``"window"``: ``W`` positions of every slot (inputs ``(slots, W,
      ..)``) at ``pos[i] .. pos[i]+W-1``.

    For the last two ``table`` is ``(slots, pages_per_slot)`` and
    ``write_pages`` / ``write_rows`` (``(slots,)`` or ``(slots, W)``)
    arrive from the host, the pool's ``no_page`` sentinel dropping the
    write of a slot that is not decoding.  The arrays are the ones the
    engine already computes; a field another kind uses is ``None``.
    ``no_page`` is that sentinel as a Python int (the shared pool's page
    count), for an op whose state is NOT that pool (a windowed entry, a
    counter) and that still has to know which slots a step serves."""

    kind: str
    table: jax.Array
    start: Optional[jax.Array] = None
    length: Optional[jax.Array] = None
    slot: Optional[jax.Array] = None
    pos: Optional[jax.Array] = None
    write_pages: Optional[jax.Array] = None
    write_rows: Optional[jax.Array] = None
    no_page: Optional[int] = None

    def live(self, width: int):
        """Which of the step's positions are real: ``(1, width)`` for a
        chunk (the rows before ``length``), ``(slots, 1)`` or ``(slots,
        W)`` for a token step or a window (the slots whose write page is
        not the sentinel)."""
        import jax.numpy as jnp
        if self.kind == "chunk":
            return (jnp.arange(width) < self.length)[None, :]
        wp = self.write_pages
        return (wp if wp.ndim == 2 else wp[:, None]) != self.no_page


class Op:
    """Base operator.  Subclasses set ``op_type`` and implement ``forward``;
    a layer that keeps something between tokens also writes the serving
    contract (``serve_state``, ``serve_check``, ``serve_step``)."""

    op_type: OpType = OpType.INPUT
    # acts on each sequence position alone: ``forward`` on one position,
    # a window or a prompt chunk IS the serving step (``serve_check``)
    position_wise: bool = False
    # the ``jax.named_scope``s the op opens INSIDE its own (which the
    # programs open round it): the parts an owner table tells apart
    # (``obs/device_ops.table_from_hlo``)
    scopes: Tuple[str, ...] = ()
    # a stack run several times with the same parameters (``models/
    # decoder_lm.py``, ``loops``): a later pass's op is ``loop_source``'s
    # call again, and what it keeps between tokens lives in THAT op's
    # leaves, which hold ``loop_passes`` regions of the pool's pages, one a
    # pass (``analysis/kv_memory.kv_cache_layout``, ``GraphDecoder._walk``)
    loop_passes: int = 1
    loop_source: Optional["Op"] = None

    def __init__(self, name: str, inputs: Sequence[Tensor]):
        self.name = name
        self.inputs: List[Tensor] = list(inputs)
        self.outputs: List[Tensor] = []
        self.weights: List[Parameter] = []
        # resolved strategy (set by FFModel.compile)
        self.parallel_config: Optional[ParallelConfig] = None

    # --- graph construction helpers -------------------------------------
    def _add_output(self, shape, dtype="float32", idx: int = 0) -> Tensor:
        t = Tensor(shape=tuple(int(s) for s in shape), dtype=dtype,
                   name=f"{self.name}:out{idx}", owner_op=self, owner_idx=idx)
        self.outputs.append(t)
        return t

    def _add_weight(self, shape, initializer, name: str, dtype="float32",
                    sharded_dim: Optional[int] = None,
                    trainable: bool = True) -> Parameter:
        p = Parameter(shape=tuple(int(s) for s in shape), dtype=dtype,
                      name=f"{self.name}/{name}", pcname=self.name,
                      initializer=initializer, sharded_dim=sharded_dim,
                      trainable=trainable)
        self.weights.append(p)
        return p

    def own_weights(self) -> List[Parameter]:
        """The parameters this op is the FIRST owner of: all of them, but for
        an op that reads another's (``FFModel.share_weights``), whose
        parameters are held, updated and all-reduced once, at the op that
        made them (``Parameter.pcname``).  What the memory and the
        weight-sync accounting go over; a call site still READS all of
        ``weights``."""
        return [w for w in self.weights if w.pcname == self.name]

    # --- execution ------------------------------------------------------
    def forward(self, params: Dict[str, jax.Array], inputs: List[jax.Array],
                ctx: OpContext) -> List[jax.Array]:
        raise NotImplementedError

    # --- serving (docs/serving.md "A layer that serves") ----------------
    def serve_state(self, slots: int, num_pages: int, page_size: int,
                    mesh_sizes: Optional[Dict[str, int]]
                    ) -> Optional[Dict]:
        """What this op keeps between tokens, or ``None`` (the default):
        ``{"kind": "kv"|"state"|"counter", "shapes": {leaf: shape},
        "entries": {leaf: PartitionSpec entries}, "dtype":
        "compute"|"f32"|"i32"}``.
        ``"kv"`` leaves are page-major, ``(num_pages, page_size, ..)``, as
        many and as wide as the op says (a K and a V pool; one latent row
        all heads share, which may add ``"values": {leaf: n}``, the values
        a token NEEDS of a row stored wider):
        they page, share prefixes, roll back and migrate; a ``"kv"`` entry
        that also declares ``"window": W`` only ever reads the last ``W``
        positions, and gets rows of its own instead of pages of the pool
        (``(slots, rows_per_slot // page_size, page_size, ..)``, a ring
        addressed by ``(slot, position)``: ``analysis/kv_memory``); a
        ``"state"`` leaf is a fixed per-slot array, and a graph holding
        one prefills whole prompts only; a ``"counter"`` leaf is something
        the op counts on the device for ``stats()`` and nothing reads back
        into the model (an op with ``"kv"`` leaves that counts too adds
        ``"counters": {"shapes", "entries"}`` to its entry and is handed
        those leaves beside its pages).  ``analysis/kv_memory.kv_cache_layout``
        collects these; the engine allocates, the static gates charge and
        ``serve_step`` receives exactly what is declared here."""
        return None

    def serve_check(self, max_seq: int) -> None:
        """Raise ``ValueError`` naming this op if it cannot generate
        streams of up to ``max_seq`` positions."""
        if not self.position_wise and \
                type(self).serve_step is Op.serve_step:
            raise ValueError(
                f"{self.name} ({self.op_type.value}) has no "
                f"single-position decode path; generation supports "
                f"causal attention, LSTM, embeddings and "
                f"position-wise ops")

    def serve_step(self, params: Dict[str, jax.Array],
                   inputs: List[jax.Array], state, where: ServeStep,
                   ctx: OpContext):
        """One serving step: ``(outputs, state)`` with ``state`` the
        declared leaves as updated (the programs donate them).  Default:
        ``forward`` on whatever positions ``inputs`` hold."""
        return self.forward(params, inputs, ctx), state

    # --- SOAP legality & cost model -------------------------------------
    def parallel_dims(self) -> Tuple[bool, ...]:
        """Which output dims may be partitioned.  Default: sample dim only
        (the reference default strategy, model.cc:263-274)."""
        nd = self.outputs[0].num_dims if self.outputs else 1
        return (True,) + (False,) * (nd - 1)

    def flops(self) -> int:
        """Forward FLOPs for the whole (unpartitioned) op."""
        return 2 * self.outputs[0].volume if self.outputs else 0

    def mxu_efficiency(self) -> float:
        """Fraction of MXU peak this op's contraction can reach (default
        1.0).  Convs with tiny input-channel counts can't fill the
        systolic array's reduction dimension — the ImageNet stem conv
        measures ~2x its ideal roofline time (calibration)."""
        return 1.0

    def backward_overhead(self, part_degrees=None) -> float:
        """Multiplier on the backward roofline for ops whose TPU
        backward lowering systematically exceeds the 2x-forward model
        (default 1.0).  Grounded in the round-5 on-chip calibration
        (BASELINE.md "Cost-model calibration"): max-pool backward lowers
        to SelectAndScatter (measured 1.9x the roofline row pool2x2),
        strided-conv dgrad to an interior-dilated conv (conv7x7/s2
        fwd+bwd measured 2.6x while its fwd alone matches).
        ``part_degrees`` is the strategy split under evaluation — ops
        whose lowering depends on HOW they're split (Pool2D: the Pallas
        kernel only runs for non-spatial splits) consult it.  Kept as an
        analytic-mode correction only — measure mode times the real
        kernels and never consults this."""
        return 1.0

    def internal_io_bytes(self, flash_attention=None) -> int:
        """HBM traffic of intermediates that never appear as op inputs or
        outputs (default none).  The roofline only sees boundary tensors;
        ops that materialize large internals (dense attention's f32 score
        matrix, batchnorm's f32 stats passes) override this — calibrated
        against on-chip measurements (``flexflow-tpu calibrate``; the
        round-5 record is seed data in search/calibration_seed.json).
        ``flash_attention`` is the run's configured kernel-selection flag
        (FFConfig.flash_attention), forwarded by the cost model so ops
        whose internal traffic depends on which kernel actually runs
        (MultiHeadAttention) can model the right one."""
        return 0

    def weight_bytes(self) -> int:
        return sum(w.volume * 4 for w in self.weights)

    def sub_problem(self, part_degrees):
        """Per-partition (input_shapes, weight_shapes) for timing ONE shard
        of this op in isolation (measure mode — the reference's sub-rect
        construction in Op::measure_compute_time, simulator.cc:235-273).

        Default: project the output partition degrees dimension-wise onto
        each input, replicating (degree 1) any input dim the degree does
        not divide — the same fallback the graph simulator applies, so
        measure mode never bans a config the analytic path allows.
        Weights stay full-size.  Ops with reduction/TP semantics (Linear,
        Conv2D, Embedding) override — a channel split shards the WEIGHT,
        not the input's feature dim.  Raises ValueError for degrees that
        are genuinely unrealizable (the simulator scores those inf)."""
        in_shapes = []
        for t in self.inputs:
            dims = snap_degrees(pad_degrees(part_degrees, t.num_dims),
                                t.shape)
            in_shapes.append(t.sub_shape(dims))
        return in_shapes, {w.name: w.shape for w in self.weights}

    def activation_bytes(self) -> int:
        return sum(t.volume * 4 for t in self.outputs)

    def __repr__(self) -> str:
        return f"{type(self).__name__}(name={self.name!r})"
