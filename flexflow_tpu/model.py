"""FFModel — graph builder + compiler + training verbs.

TPU-native re-design of the reference's god object (``include/model.h:240-429``,
``src/runtime/model.cc``):

* builder methods (``conv2d``/``dense``/… model.h:243-351) append Ops to a
  layer list exactly like the reference;
* ``compile()`` (reference model.cc:950-1010) resolves the parallel strategy
  (imported file / MCMC search / data-parallel default), builds the device
  mesh, and traces ONE fused jitted train step — where the reference
  materializes Legion regions+partitions, we emit sharding constraints and
  let XLA compile the whole iteration (forward+backward+update) into a single
  SPMD program;
* the training verbs ``init_layers/forward/backward/update/zero_gradients``
  (model.cc:897-940, 1056-1079) are kept for API parity, operating on the
  model's held state; ``fit()`` uses the fused step (the fast path — the
  reference's Legion tracing optimization, alexnet.cc:110-117, corresponds to
  XLA compiling the traced step once and replaying it).
"""

from __future__ import annotations

import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import faults
from .resilience import (MANIFEST_KEY, _atomic_savez, build_manifest,
                         read_npz_verified)

# "<anything>_step<N>.npz" — the family naming convention elastic
# checkpoints use; retention and stale-tmp sweeps operate on it
_STEP_FAMILY_RE = re.compile(r"^(?P<family>.+_step)\d+\.npz$")


def _cleanup_stale_tmps(final: str) -> None:
    """Remove orphaned ``*.tmp.npz`` siblings of ``final``: a worker
    killed mid-``np.savez`` (or a disk-full async writer) leaves them
    behind, and nothing else ever deletes them.  Scoped to the same
    checkpoint family (``<name>_step<N>`` siblings, or the exact name
    for step-less paths) so unrelated tmp files are untouched."""
    d = os.path.dirname(final) or "."
    base = os.path.basename(final)
    m = _STEP_FAMILY_RE.match(base)
    if m is not None:
        pat = re.compile(re.escape(m.group("family")) + r"\d+\.tmp\.npz$")
    else:
        pat = re.compile(re.escape(base[:-len(".npz")]) + r"\.tmp\.npz$")
    try:
        names = os.listdir(d)
    except OSError:
        return
    for n in names:
        if pat.fullmatch(n):
            try:
                os.remove(os.path.join(d, n))
            except OSError:
                pass


def _prune_step_family(final: str, keep_last: int) -> None:
    """Retention for step-numbered checkpoint families: after ``final``
    is published, keep only the newest ``keep_last`` of its
    ``<name>_step<N>.npz`` siblings.  No-op for step-less names —
    there is no family to prune."""
    m = _STEP_FAMILY_RE.match(os.path.basename(final))
    if m is None:
        return
    from .parallel.elastic import _step_checkpoints
    prefix = m.group("family")[:-len("_step")]
    d = os.path.dirname(final) or "."
    for _, p in _step_checkpoints(d, prefix)[max(1, int(keep_last)):]:
        try:
            os.remove(p)
        except OSError:
            pass

from . import losses as losses_mod
from . import metrics as metrics_mod
from .config import DeviceType, FFConfig, MemoryType, ParallelConfig
from .initializers import GlorotUniform
from .op import Op, OpContext, OpType, resolve_conv_layout
from .optimizers import Optimizer, SGDOptimizer
from .ops.conv import Conv2D, Pool2D
from .ops.elementwise import ElementBinary, ElementUnary
from .ops.linear import Embedding, Linear
from .ops.norm import BatchNorm, LayerNorm, RMSNorm
from .ops.tensor_ops import (Concat, Dropout, Flat, Reshape, Softmax, Split,
                             Transpose)
from .parallel.mesh import MachineMesh
from .parallel.sharding import batch_spec, output_spec, param_spec
from .tensor import Parameter, Tensor


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None,
                 mesh: Optional[MachineMesh] = None):
        if config is None:
            # the flexflow-tpu runner installs a parsed default (cli.py)
            import flexflow_tpu
            config = flexflow_tpu.get_default_config()
        self.config = config
        self.layers: List[Op] = []
        self.parameters: List[Parameter] = []
        self.input_tensors: List[Tensor] = []
        self.mesh = mesh
        self.label_tensor: Optional[Tensor] = None
        self.optimizer: Optional[Optimizer] = None
        self.loss_type: Optional[str] = None
        self.metrics: List[str] = []
        self._name_counts: Dict[str, int] = {}
        # ``(first op, ops a pass, passes)`` where a stretch of the layer
        # list is one stack laid several times with the same parameters
        # (``models/decoder_lm.py``, ``loops``), else None
        self.loop: Optional[Tuple[int, int, int]] = None
        self._compiled = False
        # runtime state
        self._params: Dict[str, jax.Array] = {}
        self._opt_state: Any = None
        self._step = 0
        self._batch: Optional[Tuple] = None
        self._cached_logits = None
        self._cached_grads = None
        self._cached_metric_sums = None
        # shape-bucketed AOT inference executables (forward_compiled) and
        # the per-batch-size zero label feeds they consume — both keyed
        # on batch size, both reused across predict()/serving calls
        self._fwd_compiled: Dict[Any, Any] = {}
        self._exec_digest_cache: Optional[str] = None
        # step_op_table()'s instruction -> graph-op tables, by batch shape
        self._step_op_tables: Dict[Any, Dict[str, tuple]] = {}
        self._dummy_labels: Dict[int, np.ndarray] = {}
        # serving weight quantization (ISSUE 14): "" = full-precision
        # params; "int8" after quantize_weights() replaced the eligible
        # matmul kernels in _params with int8 tensors + per-channel
        # scales (one-way for this model instance — training verbs
        # refuse to run on quantized weights)
        self._quantized: str = ""
        self._quant_report: Optional[Dict[str, Any]] = None
        # trace-time replicate-fallback sites drained so far (raw
        # (name, dim, degree, axis, axis_size, reason) tuples — the set
        # the static FF120 prediction must equal)
        self.runtime_fallback_sites: set = set()
        self.perf_metrics = metrics_mod.PerfMetrics()

    # ------------------------------------------------------------------
    # graph construction (reference model.h:243-351 builder surface)
    # ------------------------------------------------------------------
    def _uname(self, prefix: str, name: Optional[str]) -> str:
        if name:
            return name
        k = self._name_counts.get(prefix, 0)
        self._name_counts[prefix] = k + 1
        return f"{prefix}_{k}" if k else prefix

    def _register(self, op: Op) -> Op:
        self.layers.append(op)
        self.parameters.extend(op.weights)
        return op

    def create_tensor(self, shape: Sequence[int], dtype: str = "float32",
                      name: str = "input") -> Tensor:
        t = Tensor(shape=tuple(int(s) for s in shape), dtype=dtype, name=name)
        self.input_tensors.append(t)
        return t

    create_input = create_tensor

    def conv2d(self, input_tensor, out_channels, kernel_h, kernel_w, stride_h,
               stride_w, padding_h, padding_w, activation=None, groups=1,
               use_bias=True, kernel_initializer=None, bias_initializer=None,
               name=None) -> Tensor:
        op = Conv2D(self._uname("conv2d", name), input_tensor, out_channels,
                    kernel_h, kernel_w, stride_h, stride_w, padding_h,
                    padding_w, activation, use_bias, groups,
                    kernel_initializer, bias_initializer)
        return self._register(op).outputs[0]

    def pool2d(self, input_tensor, kernel_h, kernel_w, stride_h, stride_w,
               padding_h, padding_w, pool_type="max", activation=None,
               name=None) -> Tensor:
        op = Pool2D(self._uname("pool2d", name), input_tensor, kernel_h,
                    kernel_w, stride_h, stride_w, padding_h, padding_w,
                    pool_type, activation)
        return self._register(op).outputs[0]

    def dense(self, input_tensor, out_dim, activation=None, use_bias=True,
              kernel_initializer=None, bias_initializer=None,
              name=None) -> Tensor:
        op = Linear(self._uname("dense", name), input_tensor, out_dim,
                    activation, use_bias, kernel_initializer, bias_initializer)
        return self._register(op).outputs[0]

    linear = dense

    def embedding(self, input_tensor, num_entries, out_dim, aggr="sum",
                  kernel_initializer=None, name=None) -> Tensor:
        op = Embedding(self._uname("embedding", name), input_tensor,
                       num_entries, out_dim, aggr, kernel_initializer)
        return self._register(op).outputs[0]

    def lstm(self, input_tensor, hidden_size, initial_state=None,
             forget_bias=1.0, kernel_initializer=None, name=None):
        """Single-layer LSTM (reference nmt/lstm.cu cuDNN fused RNN).
        Returns ``(seq, h_n, c_n)`` tensors; pass ``initial_state=(h, c)``
        to chain encoder → decoder (nmt/rnn.h:27-158 SharedVariable graph)."""
        from .ops.rnn import LSTM
        op = LSTM(self._uname("lstm", name), input_tensor, hidden_size,
                  initial_state, forget_bias, kernel_initializer)
        self._register(op)
        return op.outputs[0], op.outputs[1], op.outputs[2]

    def pipeline_transformer_block(self, input_tensor, num_stages, num_heads,
                                   d_ff, num_microbatches=None,
                                   schedule="gpipe", virtual_stages=None,
                                   name=None) -> Tensor:
        """A stack of identical encoder blocks run as a collective pipeline
        over the 'p' mesh axis (beyond the reference — SURVEY §2.15:
        FlexFlow has no stage pipeline).  ``schedule``: "gpipe" or
        "interleaved" (requires ``virtual_stages`` chunks per rank,
        ~v-fold smaller bubble)."""
        from .ops.pipeline import PipelineTransformerBlock
        op = PipelineTransformerBlock(
            self._uname("pipeline_block", name), input_tensor, num_stages,
            num_heads, d_ff, num_microbatches, schedule=schedule,
            virtual_stages=virtual_stages)
        return self._register(op).outputs[0]

    def pipeline(self, input_tensor, num_stages, stage_builder,
                 num_microbatches=None, schedule="gpipe",
                 virtual_stages=None, name=None) -> Tensor:
        """Pipeline ``num_stages`` instances of an ARBITRARY FFModel
        subgraph over the 'p' mesh axis (beyond the reference — SURVEY
        §2.15).  ``stage_builder(seg, t)`` builds one stage against a
        fresh builder ``seg`` and probe tensor ``t`` (same shape in and
        out); the subgraph may contain dense TP layers and ``moe`` —
        composed with n/c/e sharding, this is the {n,c,e,p} program."""
        from .ops.pipeline import PipelineSegment
        op = PipelineSegment(self._uname("pipeline", name), input_tensor,
                             num_stages, stage_builder, self.config,
                             num_microbatches, schedule=schedule,
                             virtual_stages=virtual_stages)
        return self._register(op).outputs[0]

    def moe(self, input_tensor, num_experts, d_ff, k=2, capacity_factor=1.25,
            activation="gelu", aux_loss_weight=1e-2, kernel_initializer=None,
            gated=False, shared_d_ff=0, routed_scale=1.0,
            scoring="softmax", held=None, name=None) -> Tensor:
        """Mixture-of-Experts FFN: a router scored by ``scoring``
        (``"softmax"`` over the experts or ``"sigmoid"`` of each logit),
        top-``k`` renormalised (times ``routed_scale``); ``held=(first,
        count)`` where this chip has only those experts' weights of an
        expert-parallel deployment; ONE dispatch by sort — the (token,
        choice) pairs sorted by expert and two grouped products over the
        experts held (over the 'e' mesh axis each shard runs its own
        experts' groups and the parts are summed).  ``capacity_factor``
        truncates each expert's group (GShard's drop policy); ``None`` is
        dropless, which is what serves.  ``gated``: SiLU-gated experts
        without biases; ``shared_d_ff``: one shared gated expert of that
        width beside the routed ones (beyond the reference — its closest
        analogue is DLRM per-table placement, dlrm.cc:106,469)."""
        from .ops.moe import MoE
        op = MoE(self._uname("moe", name), input_tensor, num_experts, d_ff,
                 k, capacity_factor, activation, aux_loss_weight,
                 kernel_initializer, gated=gated, shared_d_ff=shared_d_ff,
                 routed_scale=routed_scale, scoring=scoring, held=held)
        return self._register(op).outputs[0]

    def latent_attention(self, input_tensor, num_heads, q_rank, kv_rank,
                         nope_dim, rope_dim, v_dim, rope_theta=10000.0,
                         eps=1e-6, kernel_initializer=None,
                         name=None) -> Tensor:
        """Causal multi-head LATENT attention (``ops/latent_attention.py``):
        queries through a rank-``q_rank`` bottleneck with a norm inside,
        keys and values expanded from ONE ``kv_rank``-wide normed row a
        token beside ``rope_dim`` rotary values all heads share; that row
        is what the serving cache holds."""
        from .ops.latent_attention import LatentAttention
        op = LatentAttention(self._uname("attention", name), input_tensor,
                             num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                             v_dim, rope_theta, eps, kernel_initializer)
        return self._register(op).outputs[0]

    def multihead_attention(self, query, key=None, value=None, embed_dim=None,
                            num_heads=8, kdim=0, vdim=0, dropout=0.0,
                            bias=True, causal=False, kernel_initializer=None,
                            num_kv_heads=None, head_dim=None, rope=None,
                            gate=False, window=0, qk_norm=None, sparse=None,
                            name=None) -> Tensor:
        """``num_kv_heads`` (grouped queries), ``head_dim`` (a head size of
        its own), ``rope`` (one layer kind's published ``rope_parameters``
        entry), ``gate`` (per-head sigmoid output gate), ``window``
        (causal attention over the last ``window`` positions, with a
        cache that holds no more), ``qk_norm`` (the eps of an RMSNorm on
        every query and key head before the rotation) and ``sparse``
        (``{"index_heads", "index_dim", "topk"}``: a learned indexer
        chooses the ``topk`` keys a query attends over) are
        ``MultiHeadAttention``'s."""
        from .ops.attention import MultiHeadAttention
        key = key if key is not None else query
        value = value if value is not None else key
        embed_dim = embed_dim or query.shape[-1]
        op = MultiHeadAttention(self._uname("attention", name), query, key,
                                value, embed_dim, num_heads, kdim, vdim,
                                dropout, bias, causal, kernel_initializer,
                                num_kv_heads=num_kv_heads, head_dim=head_dim,
                                rope=rope, gate=gate, window=window,
                                qk_norm=qk_norm, sparse=sparse)
        return self._register(op).outputs[0]

    def position_embedding(self, input_tensor, max_len=None,
                           kernel_initializer=None, name=None) -> Tensor:
        from .ops.attention import PositionEmbedding
        op = PositionEmbedding(self._uname("pos_embedding", name),
                               input_tensor, max_len, kernel_initializer)
        return self._register(op).outputs[0]

    def flat(self, input_tensor, name=None) -> Tensor:
        return self._register(Flat(self._uname("flat", name), input_tensor)).outputs[0]

    def softmax(self, input_tensor, axis=-1, name=None) -> Tensor:
        return self._register(
            Softmax(self._uname("softmax", name), input_tensor, axis)).outputs[0]

    def concat(self, tensors, axis, name=None) -> Tensor:
        return self._register(
            Concat(self._uname("concat", name), tensors, axis)).outputs[0]

    def split(self, input_tensor, sizes, axis, name=None) -> List[Tensor]:
        if isinstance(sizes, int):
            total = input_tensor.shape[axis]
            sizes = [total // sizes] * sizes
        return self._register(
            Split(self._uname("split", name), input_tensor, sizes, axis)).outputs

    def reshape(self, input_tensor, shape, name=None) -> Tensor:
        return self._register(
            Reshape(self._uname("reshape", name), input_tensor, shape)).outputs[0]

    def transpose(self, input_tensor, perm, name=None) -> Tensor:
        return self._register(
            Transpose(self._uname("transpose", name), input_tensor, perm)).outputs[0]

    def dropout(self, input_tensor, rate, seed=0, name=None) -> Tensor:
        return self._register(
            Dropout(self._uname("dropout", name), input_tensor, rate, seed)).outputs[0]

    def batch_norm(self, input_tensor, relu=True, momentum=0.9, eps=1e-5,
                   name=None) -> Tensor:
        return self._register(
            BatchNorm(self._uname("batchnorm", name), input_tensor, relu,
                      momentum, eps)).outputs[0]

    def layer_norm(self, input_tensor, eps=1e-5, name=None) -> Tensor:
        return self._register(
            LayerNorm(self._uname("layernorm", name), input_tensor, eps)).outputs[0]

    def rms_norm(self, input_tensor, eps=1e-6, name=None) -> Tensor:
        return self._register(
            RMSNorm(self._uname("rmsnorm", name), input_tensor, eps)).outputs[0]

    def exit_gate(self, states, threshold=1.0, kernel_initializer=None,
                  name=None) -> Tensor:
        """The state of the pass a token leaves a looped stack by
        (``ops/exit_gate.py``): ``states`` are the passes' normed ends, in
        order; the first whose cumulative exit mass reaches ``threshold``
        is the output."""
        from .ops.exit_gate import ExitGate
        return self._register(ExitGate(
            self._uname("exit_gate", name), states, threshold,
            kernel_initializer)).outputs[0]

    # element unary/binary builders (reference model.h: exp/relu/... adders)
    def _unary(self, fn, x, name=None, scalar=None) -> Tensor:
        return self._register(
            ElementUnary(self._uname(fn, name), x, fn, scalar)).outputs[0]

    def exp(self, x, name=None):
        return self._unary("exp", x, name)

    def relu(self, x, name=None):
        return self._unary("relu", x, name)

    def sigmoid(self, x, name=None):
        return self._unary("sigmoid", x, name)

    def tanh(self, x, name=None):
        return self._unary("tanh", x, name)

    def elu(self, x, name=None):
        return self._unary("elu", x, name)

    def gelu(self, x, name=None):
        return self._unary("gelu", x, name)

    def identity(self, x, name=None):
        return self._unary("identity", x, name)

    def scalar_multiply(self, x, scalar, name=None):
        return self._unary("scalar_mul", x, name, scalar)

    def _binary(self, fn, a, b, name=None) -> Tensor:
        return self._register(
            ElementBinary(self._uname(fn, name), a, b, fn)).outputs[0]

    def add(self, a, b, name=None):
        return self._binary("add", a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary("sub", a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary("mul", a, b, name)

    def divide(self, a, b, name=None):
        return self._binary("div", a, b, name)

    def mse_loss(self, logits: Tensor, labels_shape=None, reduction="average",
                 name=None) -> Tensor:
        """Op-form MSE loss used by DLRM (reference src/ops/mse_loss.cu:21-34):
        registers a real MSELoss op (identity pass-through whose metric sums
        ride the fused step — the reference's per-op PerfMetrics future) and
        sets the model's loss type."""
        from .ops.loss_ops import MSELoss
        op = MSELoss(self._uname("mse_loss", name), logits, reduction)
        self._register(op)
        self.loss_type = (losses_mod.MEAN_SQUARED_ERROR_AVG_REDUCE
                          if reduction == "average"
                          else losses_mod.MEAN_SQUARED_ERROR_SUM_REDUCE)
        if losses_mod.MEAN_SQUARED_ERROR not in self.metrics:
            self.metrics.append(losses_mod.MEAN_SQUARED_ERROR)
        return op.outputs[0]

    # ------------------------------------------------------------------
    # compile
    # ------------------------------------------------------------------
    def compile(self, optimizer: Optional[Optimizer] = None,
                loss_type: Optional[str] = None,
                metrics: Optional[Sequence[str]] = None,
                comp_mode: str = "training",
                mesh: Optional[MachineMesh] = None,
                final_tensor: Optional[Tensor] = None,
                verify: str = "warn") -> None:
        """Reference FFModel::compile (model.cc:950-1010): resolve strategies,
        materialize the parallel layout, create label tensor + optimizer
        state.  Our region/partition DDL is the (mesh, PartitionSpec)
        assignment; actual array allocation happens in init_layers().

        ``verify`` runs the static verifier (flexflow_tpu.analysis) over
        the resolved graph + strategies BEFORE any tracing: ``"warn"``
        (default) surfaces ERROR/WARN diagnostics as one aggregate
        warning, ``"error"`` raises :class:`analysis.VerificationError`
        on any ERROR, ``"off"`` skips the pass.  The report is kept on
        ``self.verify_report`` either way (sans "off")."""
        cfg = self.config
        self.optimizer = optimizer or self.optimizer or SGDOptimizer(
            lr=cfg.learning_rate, weight_decay=cfg.weight_decay)
        if loss_type is not None:
            self.loss_type = loss_type
        if self.loss_type is None:
            self.loss_type = losses_mod.SPARSE_CATEGORICAL_CROSSENTROPY
        self.metrics = metrics_mod.canonicalize_metrics(
            list(metrics or self.metrics or []))
        self.comp_mode = comp_mode
        self._final_tensor = final_tensor or self.layers[-1].outputs[0]
        # Reference-parity fused softmax-CE contract: the reference's loss
        # task consumes the Softmax op's *output* but computes the fused
        # gradient (softmax - onehot) as if on logits
        # (loss_functions.cu:36-74, softmax.cu:216-218).  Our sparse-CCE is
        # the fused logit form, so when the graph ends in an explicit Softmax
        # the loss must read the Softmax *input* — otherwise CE is applied to
        # probabilities (double softmax).  Predictions keep the softmax output.
        self._loss_tensor = self._final_tensor
        if (losses_mod.uses_logits(self.loss_type)
                and self._final_tensor.owner_op is not None
                and self._final_tensor.owner_op.op_type == OpType.SOFTMAX):
            self._loss_tensor = self._final_tensor.owner_op.inputs[0]

        # --- strategy resolution (reference compile step 1) ---
        if cfg.import_strategy_file:
            from .strategy.proto import load_strategy_file
            cfg.strategies.update(load_strategy_file(cfg.import_strategy_file))
        elif cfg.search_budget > 0:
            from .search.mcmc import optimize_strategies
            cfg.strategies.update(optimize_strategies(self, cfg))
        for op in self.layers:
            op.parallel_config = cfg.strategies.get(op.name)
        # reference strategies may pin parts to arbitrary processors
        # (mapper.cc:86-103); one SPMD program cannot pin individual ops
        # to chips, so parts map to mesh-linearized coordinates instead —
        # the verifier reports this as FF111 (and out-of-machine ids as
        # FF104) through _run_verifier below, replacing the old ad-hoc
        # warning with the structured diagnostic path.

        # exported BEFORE the mesh is built: search-and-export (-s) for
        # a machine this process does not own must leave its file even
        # though going on to train there is an error (below)
        if cfg.export_strategy_file:
            from .strategy.proto import save_strategy_file
            save_strategy_file(cfg.export_strategy_file,
                               {op.name: op.parallel_config
                                for op in self.layers if op.parallel_config})

        # --- mesh construction ---
        if mesh is not None:
            self.mesh = mesh
        if self.mesh is None:
            shape = cfg.mesh_shape
            if shape is None:
                shape = self._infer_mesh_shape()
            self.mesh = MachineMesh(shape)

        # --- label tensor (reference model.cc:1001-1006) ---
        if self.label_tensor is None:
            n = self._final_tensor.shape[0]
            if self.loss_type == losses_mod.SPARSE_CATEGORICAL_CROSSENTROPY:
                if self._final_tensor.num_dims == 3:
                    # per-token labels for sequence models (NMT)
                    self.label_tensor = Tensor(
                        (n, self._final_tensor.shape[1]), "int32", "label")
                else:
                    self.label_tensor = Tensor((n, 1), "int32", "label")
            else:
                self.label_tensor = Tensor(self._final_tensor.shape,
                                           "float32", "label")

        if cfg.gradient_accumulation_steps < 1:
            raise ValueError(
                f"gradient_accumulation_steps must be >= 1, got "
                f"{cfg.gradient_accumulation_steps}")
        if cfg.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got "
                f"{cfg.steps_per_dispatch}")
        self._check_accum_divisible(cfg.batch_size, "batch_size")
        self._resolve_host_placements()
        self._run_verifier(verify)
        self._build_step_fns()
        self._compiled = True

    def _run_verifier(self, verify: str) -> None:
        """The compile-time static verification pass (ISSUE 3): every
        strategy — imported .pb, searched, hand-written — is checked once,
        statically, before anything is traced or a multi-chip job burns
        time.  The scattered per-tensor replicate-fallback warnings the
        sharding layer used to emit are predicted here from the same
        predicate (analysis.legality) and surfaced once, aggregated."""
        if verify == "off":
            return
        if verify not in ("warn", "error"):
            raise ValueError(
                f"verify must be 'warn', 'error' or 'off', got {verify!r}")
        from .analysis import VerificationError, verify_compile
        report = verify_compile(self)
        self.verify_report = report
        if verify == "error" and report.errors:
            raise VerificationError(report)
        bad = report.errors + report.warnings
        if bad:
            import warnings
            warnings.warn(
                f"strategy/graph verification found {len(report.errors)} "
                f"error(s), {len(report.warnings)} warning(s):\n"
                + "\n".join(d.render() for d in bad[:20])
                + ("\n..." if len(bad) > 20 else "")
                + "\n(verify='error' makes these fatal; verify='off' "
                  "silences; flexflow-tpu lint checks strategies offline)",
                stacklevel=3)

    def _resolve_host_placements(self) -> None:
        """Host-placed parameters (reference hetero strategies: device_type
        CPU / memory ZCM) get a host-memory sharding (``pinned_host``
        where the backend has it, else its feature-detected host kind —
        :mod:`flexflow_tpu.compat`); the paired device sharding is used
        to unify memory spaces around the optimizer update."""
        from .compat import with_host_memory
        from .ops.linear import host_placed
        self._host_shardings: Dict[str, Any] = {}
        self._dev_shardings: Dict[str, Any] = {}
        for op in self.layers:
            if not host_placed(op.parallel_config):
                continue
            for p in op.weights:
                if self.mesh is not None:
                    from .parallel.sharding import param_spec as pspec
                    dev = self.mesh.sharding(
                        pspec(p, op.parallel_config, self.mesh))
                else:
                    dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
                hs = with_host_memory(dev)
                if hs is not None:
                    self._host_shardings[p.name] = hs
                    self._dev_shardings[p.name] = dev
                else:
                    import warnings
                    warnings.warn(
                        f"{p.name}: host placement requested but this "
                        f"backend has no host memory kind; keeping device "
                        f"placement")

    def _infer_mesh_shape(self) -> Dict[str, int]:
        """Derive mesh axis sizes from resolved per-op strategies: each
        canonical axis is sized to the LCM of the degrees ops assign to it
        (every degree then divides the axis and maps onto sub-axes —
        mesh.MachineMesh), falling back to the max degree when the LCM
        overshoots the device count."""
        import math

        from .parallel.mesh import dim_axis_names
        # -ll:tpu / --nodes bound the worker count (reference FFConfig)
        ndev = (self.config.num_devices if self.config.workers_per_node
                else len(jax.devices()))
        if ndev > len(jax.devices()):
            raise ValueError(
                f"-ll:tpu/--nodes request {ndev} devices but only "
                f"{len(jax.devices())} are visible "
                f"(platform {jax.devices()[0].platform}); a run asked "
                f"to train on {ndev} chips does not quietly train on "
                f"fewer")
        lcm = {"n": 1, "c": 1, "h": 1, "w": 1, "s": 1}
        mx = dict(lcm)
        any_cfg = False
        for op in self.layers:
            pc = op.parallel_config
            if pc is None:
                continue
            any_cfg = True
            axes = dim_axis_names(len(pc.dims))
            for deg, ax in zip(pc.dims, axes):
                if ax and deg > 1:
                    lcm[ax] = math.lcm(lcm[ax], deg)
                    mx[ax] = max(mx[ax], deg)
        if not any_cfg:
            return {"n": ndev}
        if int(np.prod(list(lcm.values()))) <= ndev:
            return lcm
        used = int(np.prod(list(mx.values())))
        if used > ndev:
            raise ValueError(f"strategy needs {used} devices, have {ndev}")
        return mx

    # ------------------------------------------------------------------
    # execution engine
    # ------------------------------------------------------------------
    def _run_ops(self, ops, params, values: Dict[int, jax.Array],
                 ctx: OpContext, constrain: bool) -> None:
        """Interpret a (sub)sequence of the layer list into ``values``
        (the reference's per-op IndexLauncher loop, model.cc:903-907,
        flattened into one XLA program) — shared by the plain and
        remat-segmented executors.

        Per-op precision (ISSUE 14): each op's compute dtype is resolved
        at the ONE point (``ops.common.resolve_op_dtype`` — strategy
        ``precision`` override, else the session dtype) and installed as
        ``ctx.compute_dtype`` for the duration of that op's forward, so
        every ``cast_compute`` site follows the strategy without any op
        knowing about the axis.  With no overrides the installed value
        is the session dtype for every op — traced programs are
        bit-identical to a build without the axis.

        Each op runs under ``jax.named_scope(op.name)``: metadata at
        trace time and nothing at run time, so every instruction of the
        compiled step names the graph op it came from
        (``obs/device_ops.py`` reads a profiler trace by it)."""
        from .ops.common import resolve_op_dtype
        base_dtype = ctx.compute_dtype
        for op in ops:
            ctx.compute_dtype = resolve_op_dtype(op, base_dtype)
            in_vals = [values[t.uid] for t in op.inputs]
            with jax.named_scope(op.name):
                out_vals = op.forward(params, in_vals, ctx)
                for t, v in zip(op.outputs, out_vals):
                    if constrain and op.parallel_config is not None:
                        spec = output_spec(t, op.parallel_config,
                                           self.mesh)
                        v = jax.lax.with_sharding_constraint(
                            v, self.mesh.sharding(spec))
                    values[t.uid] = v
        ctx.compute_dtype = base_dtype

    def _execute(self, params: Dict[str, jax.Array],
                 inputs: Dict[int, jax.Array], ctx: OpContext,
                 constrain: bool) -> Dict[int, jax.Array]:
        values: Dict[int, jax.Array] = dict(inputs)
        self._run_ops(self.layers, params, values, ctx, constrain)
        return values

    def _execute_remat(self, params: Dict[str, jax.Array],
                       inputs: Dict[int, jax.Array], ctx: OpContext,
                       constrain: bool,
                       keep_uids) -> Dict[int, jax.Array]:
        """sqrt(N)-segmented rematerialization: the layer list is split
        into ~sqrt(N) segments and each segment's forward is wrapped in
        ``jax.checkpoint``, so only segment-BOUNDARY tensors survive to
        the backward pass and a segment's interior is recomputed when its
        backward runs.  (A single whole-forward ``jax.checkpoint`` — the
        previous implementation — saves nothing: the backward's first
        step rematerializes every residual at once, and XLA's own
        ``memory_analysis()`` reports an unchanged high-water.)  Returns
        only boundary tensors + ``keep_uids`` — returning every
        intermediate would pin it as a saved output."""
        import dataclasses as dc
        import math as _math

        layers = self.layers
        n = len(layers)
        nseg = max(2, _math.isqrt(n))
        bounds = [round(i * n / nseg) for i in range(nseg + 1)]
        segments = [layers[a:b] for a, b in zip(bounds, bounds[1:]) if b > a]
        keep = set(keep_uids)
        # uids each segment consumes from OUTSIDE itself / produces
        seg_in, seg_out = [], []
        for seg in segments:
            produced = {t.uid for op in seg for t in op.outputs}
            seg_in.append({t.uid for op in seg for t in op.inputs}
                          - produced)
            seg_out.append(produced)
        values: Dict[int, jax.Array] = dict(inputs)
        for i, seg in enumerate(segments):
            needed_later = set(keep)
            for j in range(i + 1, len(segments)):
                needed_later |= seg_in[j]
            in_uids = sorted(u for u in seg_in[i] if u in values)
            out_uids = sorted(seg_out[i] & needed_later)

            def seg_fn(params, carry, seg=seg, in_uids=in_uids,
                       out_uids=out_uids):
                ictx = dc.replace(ctx, updates={}, aux_losses={})
                vals = dict(zip(in_uids, carry))
                self._run_ops(seg, params, vals, ictx, constrain)
                return ([vals[u] for u in out_uids],
                        ictx.updates, ictx.aux_losses)

            # the LAST segment runs un-checkpointed: its activations are
            # consumed immediately by the first backward step, so saving
            # them is free and recomputing them pure waste
            fn = seg_fn if i == len(segments) - 1 else jax.checkpoint(seg_fn)
            outs, upd, aux = fn(params, tuple(values[u] for u in in_uids))
            ctx.updates.update(upd)
            ctx.aux_losses.update(aux)
            values.update(zip(out_uids, outs))
        return values

    def _split_params(self):
        trainable = {p.name for p in self.parameters if p.trainable}
        return trainable

    def _sparse_embedding_specs(self):
        """Embedding tables eligible for the sparse-update path
        (FFConfig.sparse_embedding_updates): autodiff runs w.r.t. the
        gathered rows and the update is a scatter-add — an EXACT rewrite
        of plain SGD that avoids the dense path's ~4 full-table HBM
        passes per step (reference embedding.cu:192-228 likewise only
        touches the looked-up rows).  Eligibility: plain SGD (momentum 0,
        weight decay 0 — momentum/decay touch every row, so sparsity
        would change semantics), device-placed, unshared table, id
        tensor is a graph input (rows can be pre-gathered from the
        batch), training mode.  Returns [(op_name, table_name,
        batch_pos)]."""
        cfg = self.config
        if cfg.sparse_embedding_updates is False:
            return []
        if cfg.gradient_accumulation_steps > 1:
            # per-microbatch row gathers can't express ONE accumulated
            # update (different ids per microbatch); dense grads
            # accumulate naturally, so accumulation keeps the dense path
            return []
        from .optimizers import SGDOptimizer as _SGD
        opt = self.optimizer
        if not (isinstance(opt, _SGD) and opt.momentum == 0.0
                and opt.weight_decay == 0.0):
            return []
        from .ops.linear import Embedding as _Emb
        input_uids = [t.uid for t in self.input_tensors]
        owners: Dict[str, int] = {}
        for op in self.layers:
            for w in op.weights:
                owners[w.name] = owners.get(w.name, 0) + 1
        specs = []
        for op in self.layers:
            if not isinstance(op, _Emb):
                continue
            tname = op.w_table.name
            if (op.inputs[0].uid in input_uids
                    and owners.get(tname, 0) == 1
                    and tname not in getattr(self, "_host_shardings", {})
                    and op.w_table.trainable):
                specs.append((op.name, tname,
                              input_uids.index(op.inputs[0].uid)))
        return specs

    def _forward_values(self, params, batch_inputs, ctx, keep_uids=None):
        constrain = self.mesh is not None and self.mesh.is_distributed
        if self.config.remat and keep_uids is not None \
                and len(self.layers) > 3:
            return self._execute_remat(params, batch_inputs, ctx,
                                       constrain, keep_uids)
        return self._execute(params, batch_inputs, ctx, constrain=constrain)

    def _build_step_fns(self) -> None:
        cfg = self.config
        loss_fn = losses_mod.get_loss_fn(self.loss_type)
        trainable_names = self._split_params()
        metric_names = self.metrics
        loss_type = self.loss_type
        input_uids = [t.uid for t in self.input_tensors]
        loss_uid = self._loss_tensor.uid
        final_uid = self._final_tensor.uid

        conv_layout = resolve_conv_layout(cfg.conv_layout, self.layers)
        self.resolved_conv_layout = conv_layout  # introspection (bench)

        sparse_specs = self._sparse_embedding_specs()
        sparse_tables = {tname for _, tname, _ in sparse_specs}
        _ROWS = "__rows__"  # reserved trainable-dict prefix for row leaves

        def forward_full(params, batch, rng, training, embedding_rows=None):
            ctx = OpContext(training=training, rng=rng,
                            compute_dtype=cfg.compute_dtype, mesh=self.mesh,
                            flash_attention=cfg.flash_attention,
                            conv_layout=conv_layout,
                            embedding_rows=embedding_rows)
            inputs = {uid: x for uid, x in zip(input_uids, batch[:-1])}
            # under cfg.remat, _forward_values runs sqrt(N)-segmented
            # jax.checkpoint and returns only boundaries + these uids
            values = self._forward_values(params, inputs, ctx,
                                          keep_uids=(loss_uid, final_uid))
            aux = sum(ctx.aux_losses.values()) if ctx.aux_losses else 0.0
            return values[loss_uid], values[final_uid], ctx.updates, aux

        per_ex_fn, loss_reduction = losses_mod.get_per_example_loss_fn(
            self.loss_type)
        self._loss_reduction = loss_reduction

        def loss_and_metrics(trainable, frozen, batch, rng, aux_scale=1.0,
                             nvalid=None, base=0):
            rows = {k[len(_ROWS):]: v for k, v in trainable.items()
                    if k.startswith(_ROWS)}
            params = {**frozen, **{k: v for k, v in trainable.items()
                                   if not k.startswith(_ROWS)}}
            logits, preds, updates, aux = forward_full(
                params, batch, rng, True, embedding_rows=rows or None)
            labels = batch[-1]
            if nvalid is None:
                # aux_scale: 1 normally; 1/k for sum-reduced gradient
                # accumulation, where the k microbatch losses ADD — without
                # the scale the (batch-size-free) aux terms would count k
                # times in loss and gradients
                with jax.named_scope("loss"):
                    loss = loss_fn(logits, labels) + aux * aux_scale
                with jax.named_scope("metrics"):
                    sums = metrics_mod.compute_batch_metrics(
                        logits, labels, metric_names, loss_type)
            else:
                # masked padded-tail objective (pad_tail mode): the
                # mean/sum over the VALID rows only.  ``base`` is this
                # (micro)batch's global row offset; under accumulation
                # every microbatch contributes masked_sum/denom (+ aux/k),
                # so the k losses ADD for BOTH reductions and grads
                # accumulate without a post-divide (see _step_core)
                mb = logits.shape[0]
                with jax.named_scope("loss"):
                    mask = ((jnp.arange(mb) + base)
                            < nvalid).astype(jnp.float32)
                    total = jnp.sum(per_ex_fn(logits, labels) * mask)
                    denom = (jnp.maximum(nvalid, 1).astype(jnp.float32)
                             if loss_reduction == "mean" else 1.0)
                    loss = total / denom + aux * aux_scale
                with jax.named_scope("metrics"):
                    sums = metrics_mod.compute_batch_metrics(
                        logits, labels, metric_names, loss_type,
                        nvalid=jnp.clip(nvalid - base, 0, mb))
            return loss, (updates, preds, sums)

        grad_fn = jax.value_and_grad(loss_and_metrics, has_aux=True)

        def _step_core(params, opt_state, batch, step, nvalid):
            rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
            trainable = {k: v for k, v in params.items()
                         if k in trainable_names and k not in sparse_tables}
            frozen = {k: v for k, v in params.items()
                      if k not in trainable_names or k in sparse_tables}
            # sparse embedding path: gather rows OUTSIDE autodiff; the
            # rows join the trainable pytree so grads arrive per-row
            for op_name, tname, pos in sparse_specs:
                idx = batch[pos].astype(jnp.int32)
                trainable[_ROWS + op_name] = jnp.take(
                    params[tname], idx, axis=0)
            accum = int(cfg.gradient_accumulation_steps)
            if accum == 1:
                if nvalid is None:
                    (loss, (updates, logits, sums)), grads = grad_fn(
                        trainable, frozen, batch, rng)
                else:
                    (loss, (updates, logits, sums)), grads = grad_fn(
                        trainable, frozen, batch, rng, 1.0, nvalid, 0)
            else:
                # scan over k equal microbatches: activations live one
                # microbatch at a time, grads accumulate at param size,
                # ONE optimizer update applies below.  Loss/metric SUMS
                # are exact (equal sizes); batchnorm stats keep the last
                # microbatch's measurement (one momentum step per
                # optimizer step) — see FFConfig.gradient_accumulation_steps
                micro = tuple(
                    a.reshape((accum, a.shape[0] // accum) + a.shape[1:])
                    for a in batch)
                zero_g = jax.tree.map(jnp.zeros_like, trainable)
                mb_rows = batch[0].shape[0] // accum

                aux_scale = (1.0 / accum
                             if loss_reduction == "sum" or nvalid is not None
                             else 1.0)

                def micro_body(acc_g, i):
                    mb = tuple(a[i] for a in micro)
                    (l, (upd, _lg, s)), g = grad_fn(
                        trainable, frozen, mb, jax.random.fold_in(rng, i),
                        aux_scale, nvalid, i * mb_rows)
                    return jax.tree.map(jnp.add, acc_g, g), (l, s, upd)

                acc_g, (ls, ss, upds) = jax.lax.scan(
                    micro_body, zero_g, jnp.arange(accum))
                sums = jax.tree.map(lambda a: jnp.sum(a, axis=0), ss)
                updates = jax.tree.map(lambda a: a[-1], upds)
                if nvalid is not None:
                    # masked microbatch losses carry the GLOBAL denominator
                    # already (see loss_and_metrics), so they add and the
                    # accumulated grads are the full masked gradient for
                    # both reductions
                    loss = jnp.sum(ls)
                    grads = acc_g
                elif loss_reduction == "sum":
                    # sum-reduced loss: the full-batch objective is the
                    # SUM over examples, so accumulated grads are
                    # already the full gradient and losses add
                    loss = jnp.sum(ls)
                    grads = acc_g
                else:
                    # mean-reduced: mean of equal-size microbatch means
                    # == the full-batch mean
                    loss = jnp.mean(ls)
                    grads = jax.tree.map(lambda g: g / accum, acc_g)
            sparse_updates = {}
            if sparse_specs:
                lr = self.optimizer.lr
                for op_name, tname, pos in sparse_specs:
                    g = grads.pop(_ROWS + op_name)
                    trainable.pop(_ROWS + op_name)
                    idx = batch[pos].astype(jnp.int32).reshape(-1)
                    # negative ids must follow the DENSE path's take-VJP
                    # (sparse == dense is the pin,
                    # tests/test_sparse_embedding.py): jnp.take wraps
                    # them python-style and its VJP routes the gradient
                    # to that row, while scatter modes treat negatives
                    # as out of bounds — wrap explicitly so the -1
                    # row's gradient lands where the dense path put it
                    nrows = params[tname].shape[0]
                    idx = jnp.where(idx < 0, idx + nrows, idx)
                    g2 = g.reshape(idx.shape[0], -1)
                    # scatter-add == plain-SGD exactly: untouched rows
                    # have zero gradient, duplicate ids accumulate.
                    # mode="drop" mirrors the dense path for OUT-OF-RANGE
                    # ids too: jnp.take fills NaN on the forward (both
                    # paths see that) and its VJP DROPS the OOB
                    # gradient, so the sparse scatter must drop as well
                    # (tests/test_sparse_embedding.py pins this)
                    sparse_updates[tname] = params[tname].at[idx].add(
                        -lr * g2, mode="drop")
            host_sh = self._host_shardings
            if host_sh:
                # unify memory spaces for the elementwise update: host params
                # visit HBM for the step, then re-pin to pinned_host (the
                # reference's ZC-memory weights likewise stream through the
                # GPU for the SGD task, optimizer_kernel.cu)
                dev_sh = self._dev_shardings
                trainable = {k: (jax.device_put(v, dev_sh[k])
                                 if k in host_sh else v)
                             for k, v in trainable.items()}
                grads = {k: (jax.device_put(g, dev_sh[k])
                             if k in host_sh else g)
                         for k, g in grads.items()}
            with jax.named_scope("optimizer"):
                new_trainable, new_opt_state = self.optimizer.update(
                    trainable, grads, opt_state)
            # NOTE: updated host params leave the step in device memory; the
            # eager _repin_host() in train_batch/fit moves them back to
            # pinned_host (XLA's SPMD pass cannot yet shard an in-program
            # host-placement annotation on the output side)
            new_params = {**frozen, **updates, **new_trainable,
                          **sparse_updates}
            return new_params, new_opt_state, loss, sums

        def train_step(params, opt_state, batch, step):
            return _step_core(params, opt_state, batch, step, None)

        def train_step_masked(params, opt_state, batch, step, nvalid):
            return _step_core(params, opt_state, batch, step, nvalid)

        # --- fused multi-step dispatch (FFConfig.steps_per_dispatch) ---
        # ONE jitted donated lax.scan over a stacked (K, batch...) window:
        # params/opt_state/step thread through the carry, per-step losses
        # and metric sums stack on device, and the host re-enters Python
        # once per WINDOW instead of once per step — the TPU-native
        # analogue of the reference's per-batch-partition index launches
        # (flexflow_dataloader.cc:260-330).  The gradient-accumulation
        # scan nests INSIDE each step unchanged.
        def window_step(params, opt_state, window, step0):
            def body(carry, batch):
                params, opt_state, step = carry
                params, opt_state, loss, sums = train_step(
                    params, opt_state, batch, step)
                return (params, opt_state, step + 1), (loss, sums)

            (params, opt_state, _), (losses, sums) = jax.lax.scan(
                body, (params, opt_state, jnp.asarray(step0, jnp.int32)),
                window)
            return params, opt_state, losses, sums

        def window_step_masked(params, opt_state, window, step0, nvalid):
            # xs carries a per-step valid-row count (padded-tail mode)
            def body(carry, xs):
                batch, nv = xs
                params, opt_state, step = carry
                params, opt_state, loss, sums = train_step_masked(
                    params, opt_state, batch, step, nv)
                return (params, opt_state, step + 1), (loss, sums)

            (params, opt_state, _), (losses, sums) = jax.lax.scan(
                body, (params, opt_state, jnp.asarray(step0, jnp.int32)),
                (window, nvalid))
            return params, opt_state, losses, sums

        def eval_step(params, batch, nvalid):
            """Masked eval: only the first ``nvalid`` rows (padded tail
            batches) contribute to loss/metric sums."""
            logits, preds, _, _ = forward_full(params, batch, None, False)
            labels = batch[-1]
            mask = (jnp.arange(logits.shape[0]) < nvalid).astype(jnp.float32)
            loss_sum = jnp.sum(per_ex_fn(logits, labels) * mask)
            sums = metrics_mod.compute_batch_metrics(
                logits, labels, metric_names, loss_type, nvalid=nvalid)
            return preds, loss_sum, sums

        # a re-compile invalidates any AOT bucket executables lowered
        # from the previous _jit_forward (serving/predict re-warm
        # lazily) AND the exec digest half of their cache key
        self._fwd_compiled = {}
        self._exec_digest_cache = None
        self._step_op_tables = {}
        donate = (0, 1)
        self._train_step = jax.jit(train_step, donate_argnums=donate)
        self._train_window = jax.jit(window_step, donate_argnums=donate)
        self._train_window_masked = jax.jit(window_step_masked,
                                            donate_argnums=donate)
        self._eval_step = jax.jit(eval_step)
        # parity verbs need un-fused pieces
        self._jit_forward = jax.jit(
            lambda params, batch: forward_full(params, batch, None, False)[1])
        self._jit_grads = jax.jit(
            lambda params, batch, step: grad_fn(
                {k: v for k, v in params.items() if k in trainable_names},
                {k: v for k, v in params.items() if k not in trainable_names},
                batch,
                jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)))

    # ------------------------------------------------------------------
    # init / weights access
    # ------------------------------------------------------------------
    def _placed_param(self, p, val):
        """Place one full (host- or device-resident) parameter value
        under its resolved sharding for the CURRENT mesh — host
        placement, strategy sharding, or replication.  The one placement
        spelling shared by :meth:`init_layers` and :meth:`reshard` (the
        latter re-places live training state after a mesh change)."""
        sharding = self._param_sharding(p)
        if sharding is None:
            return jnp.asarray(val)
        if p.name in getattr(self, "_host_shardings", {}):
            return jax.device_put(val, sharding)
        return self._put_global(val, sharding)

    def _param_sharding(self, p):
        """The sharding :meth:`_placed_param` places ``p`` under, or
        None (the default device): what describes a parameter that is
        not installed yet (``GraphDecoder._program_specs``)."""
        if p.name in getattr(self, "_host_shardings", {}):
            return self._host_shardings[p.name]
        if self.mesh is not None and self.mesh.is_distributed:
            pc = None
            for lop in self.layers:
                if p in lop.weights:
                    pc = lop.parallel_config
                    break
            return self.mesh.sharding(param_spec(p, pc, self.mesh))
        return None

    def _trainable_on_device(self, params: Dict[str, jax.Array]
                             ) -> Dict[str, jax.Array]:
        """The trainable subset of ``params`` with host-placed entries
        re-pinned to their device shardings (optimizer slots live in
        device memory even for host params) — the pytree optimizer
        state is built from/around."""
        trainable = {}
        for k, v in params.items():
            if k not in self._split_params():
                continue
            if k in getattr(self, "_host_shardings", {}):
                v = jax.device_put(v, self._dev_shardings[k])
            trainable[k] = v
        return trainable

    def init_layers(self, seed: Optional[int] = None) -> None:
        """Reference init_layers (model.cc:897-901): run per-op init tasks.
        Here: initialize every Parameter on device with its sharding."""
        assert self._compiled, "call compile() first"
        seed = self.config.seed if seed is None else seed
        key = jax.random.PRNGKey(seed)
        params: Dict[str, jax.Array] = {}
        for i, p in enumerate(self.parameters):
            sub = jax.random.fold_in(key, i)
            init = p.initializer or GlorotUniform()
            val = init(sub, p.shape, jnp.dtype(self.config.param_dtype)
                       if p.dtype == "float32" else jnp.dtype(p.dtype))
            params[p.name] = self._placed_param(p, val)
        self._params = params
        self._opt_state = self.optimizer.init_state(
            self._trainable_on_device(params))
        self._step = 0

    def share_weights(self, op: Op, source_op: Op) -> None:
        """Make ``op`` read ``source_op``'s parameters — keras shared-layer
        reuse (the reference's graph model re-uses one weight region across
        calls; here two ops reference the same Parameter objects, so the
        params dict holds one entry and autodiff sums both call sites'
        gradients automatically)."""
        assert len(op.weights) == len(source_op.weights), \
            (op.name, source_op.name)
        for w_new, w_old in zip(list(op.weights), source_op.weights):
            assert tuple(w_new.shape) == tuple(w_old.shape), \
                (w_new.name, w_new.shape, w_old.shape)
            for attr, val in list(vars(op).items()):
                if val is w_new:
                    setattr(op, attr, w_old)
            self.parameters = [p for p in self.parameters if p is not w_new]
        op.weights = list(source_op.weights)

    def get_parameter_by_name(self, name: str) -> Optional[Parameter]:
        for p in self.parameters:
            if p.name == name or p.name.endswith("/" + name):
                return p
        return None

    def get_weights(self, name: str) -> np.ndarray:
        """Reference Parameter::get_weights (model.cu:319-370)."""
        return np.asarray(self._params[self._resolve(name)])

    def set_weights(self, name: str, value: np.ndarray) -> None:
        key = self._resolve(name)
        cur = self._params[key]
        val = jnp.asarray(value, cur.dtype).reshape(cur.shape)
        if self.mesh is not None and self.mesh.is_distributed:
            val = self._put_global(val, cur.sharding)
        self._params[key] = val

    # ------------------------------------------------------------------
    # checkpoint / resume (beyond the reference: it persists nothing but
    # strategy files — SURVEY §5 "no model checkpointing")
    # ------------------------------------------------------------------
    @staticmethod
    def _put_global(val, sharding):
        """Place a host-resident full array under ``sharding``.  In
        multi-process runs a sharding spanning non-addressable devices
        cannot be device_put directly; each process contributes its
        addressable shards instead (every process holds the same full
        value — deterministic init/feeds), the multi-controller SPMD
        contract of the reference's GASNet path (FlexFlow.mk:68-69)."""
        if jax.process_count() > 1 and not sharding.is_fully_addressable:
            arr = np.asarray(val)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return jax.device_put(val, sharding)

    @staticmethod
    def _gather_host(v) -> np.ndarray:
        """Fetch an array to host numpy, allgathering across processes for
        multi-host shardings (np.asarray alone raises on arrays that are
        not fully addressable)."""
        if jax.process_count() > 1 and not v.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(v,
                                                               tiled=True))
        return np.asarray(v)

    @staticmethod
    def _ckpt_path(path: str) -> str:
        # np.savez silently appends '.npz' to suffix-less paths; normalize
        # here so save/load agree on the on-disk name
        return path if path.endswith(".npz") else path + ".npz"

    def save_checkpoint(self, path: str, async_write: bool = False,
                        keep_last: Optional[int] = None) -> None:
        """Write params + optimizer state + step to one ``.npz``.  In
        multi-host runs every process participates in the gather, only
        process 0 writes the file, and all processes synchronize after the
        write so peers never read a partially written checkpoint from
        shared storage.

        ``async_write=True`` overlaps the serialization with training:
        the device->host GATHER stays synchronous (the live buffers may
        be donated by the very next step), but the np.savez + atomic
        rename — the slow disk half for multi-GB models — runs in a
        background thread.  Single-process only (the multi-host barrier
        must observe the completed write); a later save/load/exit joins
        the pending writer first via :meth:`wait_for_checkpoint`.

        The file embeds an integrity manifest (per-array CRC32 + step +
        format version, under ``meta:manifest``) which
        :meth:`load_checkpoint` and ``resilience.verify_checkpoint``
        check before trusting the file.  ``keep_last=K`` prunes older
        ``<name>_step<N>.npz`` siblings after a successful publish so
        long elastic runs do not fill the disk; stale ``*.tmp.npz``
        orphans from killed writers are swept on every save."""
        self._check_not_quantized("save_checkpoint")
        flat: Dict[str, np.ndarray] = {}
        for k, v in self._params.items():
            flat[f"param:{k}"] = self._gather_host(v)
        leaves, treedef = jax.tree_util.tree_flatten(self._opt_state)
        for i, leaf in enumerate(leaves):
            flat[f"opt:{i}"] = self._gather_host(leaf)
        flat["meta:step"] = np.asarray(self._step, np.int64)
        self.wait_for_checkpoint()  # one writer at a time, in order
        if jax.process_index() == 0:
            # atomic publish (resilience._atomic_savez): a crash/kill
            # mid-save must never leave a truncated file at the final
            # name — a corrupt "newest" checkpoint would cost every
            # elastic restart one verification-and-fallback pass
            # (parallel/elastic.py resumes newest-valid by step).
            final = self._ckpt_path(path)
            _cleanup_stale_tmps(final)
            step = self._step
            # topology snapshot for the v2 manifest, captured NOW (the
            # async writer thread must describe the mesh the state was
            # gathered under, not whatever a later reshard() moved to)
            mesh_shape = self._live_mesh_shape()
            num_devices = self.mesh.num_devices if self.mesh else 1
            process_count = jax.process_count()
            digest = self._strategy_digest()

            def write():
                # manifest here: writing rank only (the N-1 non-writers
                # never need the CRC pass), and under async_write the
                # full-state CRC runs in the background thread with the
                # rest of the slow serialization half, not on the train
                # loop (flat is fully materialized at this point)
                flat[MANIFEST_KEY] = np.asarray(
                    build_manifest(flat, step, mesh_shape=mesh_shape,
                                   num_devices=num_devices,
                                   process_count=process_count,
                                   strategy_digest=digest))
                _atomic_savez(final, flat)
                faults.maybe_corrupt_checkpoint(final, step)
                if keep_last is not None:
                    _prune_step_family(final, keep_last)

            if async_write and jax.process_count() == 1:
                def guarded():
                    try:
                        write()
                    except BaseException as e:
                        # loud even if nothing ever joins (a script may
                        # exit right after an async save): print the
                        # traceback from the thread, AND store for
                        # re-raise at the next save/load/wait
                        import traceback
                        traceback.print_exc()
                        self._ckpt_exc = e

                import threading
                # non-daemon: the interpreter joins it at exit, so a
                # script whose last act is an async save still publishes
                self._ckpt_writer = threading.Thread(
                    target=guarded, name="ff-ckpt-writer")
                self._ckpt_writer.start()
            else:
                write()  # sync path: failures raise directly, untouched
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("ff_checkpoint_written")

    def _raise_ckpt_exc(self):
        exc = getattr(self, "_ckpt_exc", None)
        if exc is not None:
            self._ckpt_exc = None
            raise RuntimeError("checkpoint write failed") from exc

    def wait_for_checkpoint(self) -> None:
        """Join a pending async checkpoint writer; re-raises any write
        failure (a silently missing checkpoint would roll training back
        on the next restore)."""
        w = getattr(self, "_ckpt_writer", None)
        if w is not None:
            w.join()
            self._ckpt_writer = None
        self._raise_ckpt_exc()

    def load_checkpoint(self, path: str) -> None:
        """Restore a checkpoint written by :meth:`save_checkpoint`,
        re-applying each parameter's sharding (incl. host placement).
        Verifies integrity first — a truncated/bit-rotted file raises
        ``resilience.CorruptCheckpointError`` naming the path (instead
        of an opaque ``zipfile.BadZipFile``), and the embedded manifest's
        per-array CRC32s are checked — then validates the full key set,
        all BEFORE mutating any state, so a corrupt file or a graph /
        optimizer mismatch fails cleanly instead of half-restoring."""
        assert self._compiled, "call compile() + init_layers() first"
        self.wait_for_checkpoint()  # never read under a pending writer
        path = self._ckpt_path(path)
        data = read_npz_verified(path, what="checkpoint")
        # validate the checkpoint against THIS model before anything
        # mutates: reshard-on-resume zero-fills params/opt state ahead
        # of the restore, so a graph/optimizer mismatch discovered
        # after it would leave the model destroyed, not untouched
        # (shapes here are GLOBAL, so the check is mesh-independent)
        self._validate_restore(data)
        # topology mismatch (checkpoint saved on a different mesh) is a
        # recoverable event, not an error: re-resolve strategies for the
        # mesh we are actually on, THEN restore the global arrays under
        # the (possibly new) shardings — reshard-on-resume
        self._reshard_if_mesh_changed(data, path)
        self._restore_from_host(data)

    def _validate_restore(self, data: Dict[str, np.ndarray]) -> None:
        """Raise ``ValueError`` unless ``data`` matches this model's
        parameter set/shapes and optimizer slot count/shapes (all
        global, hence mesh-independent) — the no-mutation gate shared
        by :meth:`load_checkpoint` and ``resilience.elastic_resume``,
        run BEFORE reshard-on-resume can zero-fill state."""
        keys = set(data) - {MANIFEST_KEY}
        ckpt_params = {k[len("param:"):] for k in keys
                       if k.startswith("param:")}
        cur_params = set(self._params)
        if ckpt_params != cur_params:
            missing = sorted(cur_params - ckpt_params)
            extra = sorted(ckpt_params - cur_params)
            raise ValueError(
                f"checkpoint does not match this model: "
                f"missing params {missing[:5]}, unexpected {extra[:5]}")
        bad_shapes = [
            (n, data[f"param:{n}"].shape, tuple(self._params[n].shape))
            for n in sorted(ckpt_params)
            if data[f"param:{n}"].shape != tuple(self._params[n].shape)]
        if bad_shapes:
            raise ValueError(
                f"checkpoint does not match this model: shape "
                f"mismatches {bad_shapes[:5]}")
        leaves, treedef = jax.tree_util.tree_flatten(self._opt_state)
        n_opt = sum(1 for k in keys if k.startswith("opt:"))
        if n_opt != len(leaves):
            raise ValueError(
                f"optimizer state mismatch: checkpoint has {n_opt} "
                f"slots, this optimizer has {len(leaves)} (was it saved "
                f"with a different optimizer?)")
        for i, leaf in enumerate(leaves):
            if data[f"opt:{i}"].shape != tuple(leaf.shape):
                raise ValueError(
                    f"optimizer state mismatch: slot {i} shape "
                    f"{data[f'opt:{i}'].shape} != {tuple(leaf.shape)}")

    def _restore_from_host(self, data: Dict[str, np.ndarray]) -> None:
        """Apply already-read (and already CRC-verified) checkpoint
        arrays — the shared tail of :meth:`load_checkpoint` and
        ``resilience.elastic_resume`` (which probes candidate files
        with ``read_npz_verified`` and must not pay a second full read +
        CRC pass for the winner).  Both callers run
        :meth:`_validate_restore` BEFORE reshard-on-resume — that is
        the load-bearing no-mutation gate, not repeated here."""
        assert self._compiled, "call compile() + init_layers() first"
        keys = set(data) - {MANIFEST_KEY}
        ckpt_params = {k[len("param:"):] for k in keys
                       if k.startswith("param:")}
        leaves, treedef = jax.tree_util.tree_flatten(self._opt_state)
        for name in ckpt_params:
            cur = self._params[name]
            val = data[f"param:{name}"].astype(cur.dtype)
            self._params[name] = self._put_global(val, cur.sharding)
        new_leaves = []
        for i, leaf in enumerate(leaves):
            arr = data[f"opt:{i}"].astype(leaf.dtype)
            new_leaves.append(self._put_global(arr, leaf.sharding))
        self._opt_state = jax.tree_util.tree_unflatten(treedef,
                                                       new_leaves)
        self._step = int(data["meta:step"])

    def _resolve(self, name: str) -> str:
        if name in self._params:
            return name
        for k in self._params:
            if k.endswith("/" + name) or k.split("/")[0] == name:
                return k
        raise KeyError(name)

    # ------------------------------------------------------------------
    # live elastic resharding (docs/elastic.md "Resharding"): a mesh
    # grow/shrink is a recoverable event, not a restart-the-world crash
    # ------------------------------------------------------------------
    def _live_mesh_shape(self) -> Optional[Dict[str, int]]:
        """Axis sizes > 1 of the current mesh (the canonical spelling
        manifests and reshard events record; {} for a 1-device mesh)."""
        if self.mesh is None:
            return None
        return {a: s for a, s in self.mesh.sizes.items() if s > 1}

    def _strategy_digest(self) -> str:
        """Digest of the resolved per-op strategy assignment (see
        strategy.proto.strategy_digest) — recorded in checkpoint
        manifests, compared at resume."""
        from .strategy.proto import strategy_digest
        return strategy_digest(
            {op.name: op.parallel_config for op in self.layers})

    def _reshard_budget(self) -> int:
        """The search budget a reshard point may spend: the dedicated
        ``reshard_search_budget`` when set, else the run's
        ``search_budget`` (the ONE fallback rule, shared by reshard /
        reshard-on-resume / the fault consumer)."""
        cfg = self.config
        return (cfg.reshard_search_budget
                if cfg.reshard_search_budget is not None
                else cfg.search_budget)

    def reshard(self, new_mesh=None, num_devices: Optional[int] = None,
                research: Optional[bool] = None,
                verify: str = "warn",
                redistribute: bool = True) -> Dict[str, Any]:
        """Move LIVE training state onto a different mesh, in process —
        the scale-up/down verb the elastic stack uses between dispatch
        windows instead of restarting the world from a checkpoint.

        Pass exactly one of ``new_mesh`` (a :class:`MachineMesh` or a
        mesh-shape dict, used as given) or ``num_devices`` (a device
        count; the mesh factorization is re-searched when re-search is
        on, else pure data parallel).  Steps, in order:

        1. **re-search** (``research``; default: on when the configured
           budget — ``cfg.reshard_search_budget``, falling back to
           ``cfg.search_budget`` — is > 0): re-run the SOAP strategy
           search for the TARGET device count through the delta-sim
           ``SimSession`` fast path (PR 1) and adopt the winning
           strategies; an explicit ``new_mesh`` pins the search to
           that factorization, so the strategies adopted are always
           expressible on the mesh actually installed;
        2. **verify**: the ``ffcheck`` static legality passes run
           against the new mesh + strategies before anything moves
           (``verify="error"`` aborts with the model UNCHANGED);
        3. **re-trace**: step/eval/window programs are rebuilt for the
           new mesh (compiled lazily at next dispatch through the
           persistent compile cache; AOT inference buckets re-lower the
           same way), and
        4. **redistribute**: params and optimizer state are gathered to
           full values and ``device_put`` into the new shardings — the
           host copy of training state (step counter, metrics) is
           untouched, and the redistribution is value-lossless
           (checkpoint arrays are full/global, so post-reshard math on
           mesh B is bit-identical to a run that was always on mesh B
           from this state — tests/test_reshard.py pins it).

        Single-controller only: in a multi-process world a mesh change
        goes through the supervisor (degrade-and-continue +
        reshard-on-resume).  Concurrency: a serving dispatcher attached
        to the model keeps working across the move (executables are
        looked up through the model's bucket cache, which this method
        invalidates after the state swap) — a dispatch racing the swap
        itself may fail transiently, which the engine's error path
        turns into failed futures for that one batch, never a wedge.
        ``redistribute=False`` skips moving the VALUES (params/opt
        slots come out zero-filled under the new shardings) — for
        callers about to overwrite every value anyway, like
        reshard-on-resume, which restores from the checkpoint right
        after; a multi-GB recovery should not pay a full gather+put of
        state it is about to discard.  Returns a small report dict
        (old/new mesh, device counts, whether re-search ran)."""
        self._check_not_quantized("reshard")
        assert self._compiled, "call compile() + init_layers() first"
        if (new_mesh is None) == (num_devices is None):
            raise ValueError("pass exactly one of new_mesh / num_devices")
        cfg = self.config
        self.wait_for_checkpoint()  # the pending writer reads _params
        mesh: Optional[MachineMesh] = None
        if new_mesh is not None:
            mesh = (new_mesh if isinstance(new_mesh, MachineMesh)
                    else MachineMesh(dict(new_mesh)))
            ndev = mesh.num_devices
        else:
            ndev = int(num_devices)
            if not 1 <= ndev <= len(jax.devices()):
                raise ValueError(
                    f"num_devices={ndev} not in [1, {len(jax.devices())}]")
        if research is None:
            research = self._reshard_budget() > 0
        old_shape = self._live_mesh_shape()
        old_ndev = self.mesh.num_devices if self.mesh else 1

        # ---- re-search strategies for the target machine (delta-sim
        # SimSession path inside search()), adopting the searched mesh
        # when the caller gave only a device count; an EXPLICIT mesh
        # pins the search to that factorization — adopting strategies
        # scored for a different one would silently replicate at trace
        # time (FF106) instead of using the searched placement
        new_strategies = None
        if research:
            from .search.mcmc import optimize_strategies
            new_strategies, best_mesh = optimize_strategies(
                self, cfg, num_devices=ndev,
                budget=self._reshard_budget(), with_mesh=True,
                mesh_shape=None if mesh is None else mesh.sizes)
            if mesh is None:
                shape = {a: s for a, s in best_mesh.items() if s > 1}
                mesh = MachineMesh(shape or {"n": 1})
        elif mesh is None:
            mesh = MachineMesh({"n": ndev})

        # ---- commit the new mesh + strategies, verify, rebuild; any
        # verification error rolls back before state has moved
        old_mesh_obj = self.mesh
        old_configs = [op.parallel_config for op in self.layers]
        if new_strategies is not None:
            for op in self.layers:
                op.parallel_config = new_strategies.get(op.name)
        self.mesh = mesh

        def _rollback():
            # params/opt_state were never reassigned: restoring mesh +
            # configs (+ the structures derived from them) returns the
            # model to a fully trainable old-mesh state
            self.mesh = old_mesh_obj
            for op, pc in zip(self.layers, old_configs):
                op.parallel_config = pc
            self._resolve_host_placements()

        try:
            self._resolve_host_placements()
            self._run_verifier(verify)
        except Exception:
            _rollback()
            raise

        # ---- rebuild + redistribute; a failure here (device OOM on a
        # grow, a lowering error) also rolls the model back whole —
        # cfg is only mutated after everything committed.  Values move
        # as full host arrays -> new shardings; the optimizer pytree is
        # rebuilt around the re-placed trainables so each slot leaf
        # lands under exactly the sharding a fresh init_state would
        # give it, then the SAVED slot values are put back
        # leaf-for-leaf (same optimizer, same structure).  Without
        # ``redistribute`` the new arrays are zero-filled sharding
        # templates (see docstring).
        try:
            # gather full state only now that verification passed: a
            # verify="error" abort stays free (no multi-GB device-to-
            # host gather paid for a reshard that never happens, no
            # host copies held live across the re-search above); the
            # old arrays' shardings are self-contained, so gathering
            # after the mesh commit is value-identical
            host_params = host_leaves = None
            if redistribute:
                host_params = {k: self._gather_host(v)
                               for k, v in self._params.items()}
                leaves, _ = jax.tree_util.tree_flatten(self._opt_state)
                host_leaves = [self._gather_host(v) for v in leaves]
            self._build_step_fns()  # also drops stale AOT buckets
            if redistribute:
                new_params = {
                    p.name: self._placed_param(p, host_params[p.name])
                    for p in self.parameters}
            else:
                # host (calloc) zeros, NOT jnp.zeros: a full global-shape
                # device allocation would OOM device 0 on exactly the
                # large sharded models this cheap path exists for
                new_params = {
                    p.name: self._placed_param(
                        p, np.zeros(self._params[p.name].shape,
                                    self._params[p.name].dtype))
                    for p in self.parameters}
            proto_state = self.optimizer.init_state(
                self._trainable_on_device(new_params))
            if redistribute:
                proto_leaves, proto_def = jax.tree_util.tree_flatten(
                    proto_state)
                assert len(proto_leaves) == len(host_leaves), \
                    (len(proto_leaves), len(host_leaves))
                new_opt = jax.tree_util.tree_unflatten(proto_def, [
                    self._put_global(np.asarray(hv, pv.dtype), pv.sharding)
                    for hv, pv in zip(host_leaves, proto_leaves)])
            else:
                new_opt = proto_state  # zeros under the right shardings
        except Exception:
            _rollback()
            self._build_step_fns()  # re-trace for the restored mesh
            raise
        self._params = new_params
        self._opt_state = new_opt
        # a serving dispatcher racing this reshard may have re-lowered
        # a bucket between the rebuild above and the params swap,
        # caching an executable bound to the OLD params' shardings —
        # drop any such entry now that the new params are visible (an
        # in-flight dispatch can still fail transiently; the engine
        # fails only that batch's futures and re-lowers fresh); the
        # mesh/strategies changed, so the exec digest changes with it
        self._fwd_compiled = {}
        self._exec_digest_cache = None
        if new_strategies is not None:
            cfg.strategies.update(new_strategies)
        cfg.mesh_shape = self._live_mesh_shape() or {"n": 1}
        # stale per-batch caches placed under the old mesh
        self._batch = None
        self._cached_logits = None
        self._cached_grads = None

        report = {"old_mesh": old_shape, "new_mesh": self._live_mesh_shape(),
                  "old_devices": old_ndev, "new_devices": mesh.num_devices,
                  "researched": bool(research), "step": self._step,
                  "strategy_digest": self._strategy_digest()}
        from .fflogger import get_logger
        get_logger("elastic").event("reshard", **report)
        return report

    def _reshard_if_mesh_changed(self, data: Dict[str, np.ndarray],
                                 path: str = "<checkpoint>") -> bool:
        """Reshard-on-resume detection: compare an already-read
        checkpoint's v2 manifest topology against the mesh this model
        is compiled for.  On a mesh change, emit one structured
        ``reshard_on_resume`` event and — when re-search is configured
        (``reshard_search_budget``/``search_budget`` > 0) — re-run
        strategy search for the CURRENT device count via
        :meth:`reshard` so the resumed run uses strategies searched for
        the machine it actually has, not the machine that died.  v1 /
        manifest-less checkpoints carry no topology and change nothing.
        Returns True when a mismatch was detected."""
        from .resilience import manifest_meta
        meta = manifest_meta(data)
        if meta is None:
            return False
        cur_shape = self._live_mesh_shape() or {}
        cur_ndev = self.mesh.num_devices if self.mesh else 1
        saved_shape = meta.get("mesh_shape")
        saved_ndev = meta.get("num_devices")
        mesh_changed = (
            (saved_ndev is not None and saved_ndev != cur_ndev)
            or (saved_shape is not None and saved_shape != cur_shape))
        cur_digest = self._strategy_digest()
        saved_digest = meta.get("strategy_digest")
        digest_changed = saved_digest not in (None, cur_digest)
        if not (mesh_changed or digest_changed):
            return False
        research = mesh_changed and self._reshard_budget() > 0
        from .fflogger import get_logger
        get_logger("elastic").event(
            "reshard_on_resume", path=path,
            saved_mesh=saved_shape, saved_devices=saved_ndev,
            mesh=cur_shape, devices=cur_ndev,
            saved_digest=saved_digest, digest=cur_digest,
            research=bool(research))
        if research:
            # searched-for-THIS-machine strategies (and factorization);
            # the caller restores the global arrays right after, under
            # whatever shardings this resolves to — so skip moving the
            # about-to-be-overwritten values (redistribute=False)
            self.reshard(num_devices=cur_ndev, redistribute=False)
        return True

    def _apply_fault_reshard(self, kind: str,
                             devices: Optional[int] = None) -> None:
        """Consume a ``grow_at_step``/``shrink_at_step`` fault request
        (faults.reshard_at_window): default scaling doubles/halves the
        current device count (capped at the visible devices, floored at
        1), landing on the data axis via ``mesh.scaled_shape`` unless a
        re-search adopts a different factorization."""
        cur = self.mesh.num_devices if self.mesh else 1
        if devices is None:
            devices = cur * 2 if kind == "grow_at_step" else max(1, cur // 2)
        devices = max(1, min(int(devices), len(jax.devices())))
        if devices == cur:
            return
        from .parallel.mesh import scaled_shape
        if self._reshard_budget() > 0:
            self.reshard(num_devices=devices)
        else:
            self.reshard(MachineMesh(
                scaled_shape(self.mesh.sizes, devices)))

    # ------------------------------------------------------------------
    # training verbs (API parity with model.cc:897-940)
    # ------------------------------------------------------------------
    def set_batch(self, *arrays) -> None:
        self._batch = tuple(self._shard_batch(arrays))

    def _batch_entries(self, shape, dtype):
        """PartitionSpec entries for one batch-leading array of ``shape``/
        ``dtype`` under the current mesh — shared by the per-batch and
        stacked-window placement paths."""
        ndim = len(shape)
        # dim 1 is a sequence dim only for (n, s) token ids or
        # (n, s, d) activations — never for image (n,c,h,w) inputs
        seq_shaped = (ndim == 3
                      or (ndim == 2 and jnp.issubdtype(dtype, jnp.integer)))
        spec = batch_spec(ndim, self.mesh,
                          seq_sharded=(seq_shaped and
                                       self.mesh.axis_size("s") > 1))
        # non-divisible dims replicate (the reference likewise backs
        # off to a dividing parallelism degree, model.cc:263-274)
        return [ax if ax is None or
                shape[i] % self.mesh.axis_size(ax) == 0 else None
                for i, ax in enumerate(spec)]

    def _shard_batch(self, arrays, entries_fn=None):
        """Place batch arrays under the mesh; ``entries_fn`` picks the
        PartitionSpec entries per array (default: the training-batch
        spec; inference passes `_infer_batch_entries` so placement and
        the AOT lowering share one spec source)."""
        entries_fn = entries_fn or self._batch_entries
        out = []
        for a in arrays:
            a = jnp.asarray(a)
            if self.mesh is not None and self.mesh.is_distributed:
                entries = entries_fn(a.shape, a.dtype)
                a = self._put_global(
                    a, self.mesh.sharding(jax.sharding.PartitionSpec(*entries)))
            out.append(a)
        return out

    def _infer_batch_entries(self, shape, dtype):
        """Inference-batch PartitionSpec entries: :meth:`_batch_entries`
        with ONE extra rule — never shard the batch dim below 2 rows
        per shard.  A 1-row shard lowers the matmuls to matrix-VECTOR
        kernels whose accumulation order differs ~1 ulp from the
        matrix-matrix path, so a request's bits would depend on which
        bucket the batcher packed it into; serving promises
        packing-invariant results (tests/test_serving.py pins engine ==
        predict bit-identically across buckets)."""
        entries = self._batch_entries(shape, dtype)
        if (entries and entries[0] is not None
                and shape[0] < 2 * self.mesh.axis_size(entries[0])):
            entries = [None] + list(entries[1:])
        return entries

    def _shard_infer_batch(self, arrays):
        """Place an inference batch exactly as the bucket executables
        (:meth:`forward_compiled`) were lowered to expect — AOT
        compiled programs validate input shardings, so placement and
        lowering must share one spec source (`_infer_batch_entries`)."""
        return self._shard_batch(arrays, self._infer_batch_entries)

    def _shard_window(self, arrays):
        """Place stacked ``(w, batch...)`` window arrays (fused multi-step
        dispatch): the leading step dim replicates; each per-step slice
        gets exactly the sharding :meth:`_shard_batch` would give it, so
        the scanned step sees the same batch layout as a direct dispatch."""
        out = []
        for a in arrays:
            a = jnp.asarray(a)
            if self.mesh is not None and self.mesh.is_distributed:
                entries = self._batch_entries(a.shape[1:], a.dtype)
                a = self._put_global(
                    a, self.mesh.sharding(
                        jax.sharding.PartitionSpec(None, *entries)))
            out.append(a)
        return out

    def forward(self):
        assert self._batch is not None, "set_batch() first"
        self._cached_logits = self._jit_forward(self._params, self._batch)
        return self._cached_logits

    def zero_gradients(self):
        self._cached_grads = None

    def backward(self):
        assert self._batch is not None
        (loss, (updates, logits, sums)), grads = self._jit_grads(
            self._params, self._batch, self._step)
        self._cached_grads = grads
        self._cached_logits = logits
        self._cached_metric_sums = sums
        self._params.update(updates)
        self.perf_metrics.update({k: np.asarray(v) for k, v in sums.items()})
        return loss

    def update(self):
        assert self._cached_grads is not None, "backward() first"
        trainable_names = self._split_params()
        trainable = {k: v for k, v in self._params.items()
                     if k in trainable_names}
        new_trainable, self._opt_state = self.optimizer.update(
            trainable, self._cached_grads, self._opt_state)
        self._params.update(new_trainable)
        self._step += 1
        self._cached_grads = None

    # ------------------------------------------------------------------
    # fit / evaluate / predict (fused fast path)
    # ------------------------------------------------------------------
    def _repin_host(self) -> None:
        """Move host-placed params back to pinned_host after a step (async
        eager transfer; see note in train_step)."""
        for k, sh in self._host_shardings.items():
            self._params[k] = jax.device_put(self._params[k], sh)

    def warmup_compile(self, *arrays) -> None:
        """Compile the fused train step for ``arrays`` WITHOUT executing it.

        Two uses: (a) pay the one-time XLA compile before fenced timing
        (the reference's warm-up iterations before its ELAPSED fence,
        alexnet.cc:102-118); (b) in multi-controller runs, compile on
        every process BEFORE the first execution — the backend's
        collective-context rendezvous at first execute has a short
        deadline, and per-process compile skew can exceed it (pair with
        ``parallel.distributed.coordination_barrier``).

        Whenever fit() will dispatch windows (``steps_per_dispatch=K > 1``
        or ``pad_tail_batches``) this also lowers the fused window
        program at width K, masked or plain to match.  A dataset whose
        step count does not divide by K still compiles its one SHORTER
        tail window at first dispatch — warmup cannot know the dataset
        length.
        """
        batch = tuple(self._shard_batch(arrays))
        self._train_step.lower(self._params, self._opt_state, batch,
                               self._step).compile()
        k = int(self.config.steps_per_dispatch)
        if k > 1 or self.config.pad_tail_batches:
            host = tuple(np.stack([np.asarray(a)] * k) for a in arrays)
            window = tuple(self._shard_window(host))
            if self.config.pad_tail_batches:
                nv = jnp.full((k,), window[0].shape[1], jnp.int32)
                self._train_window_masked.lower(
                    self._params, self._opt_state, window, self._step,
                    nv).compile()
            else:
                self._train_window.lower(self._params, self._opt_state,
                                         window, self._step).compile()

    def step_op_table(self, *arrays) -> Dict[str, tuple]:
        """Which graph op each instruction of the compiled train step
        belongs to, for ``arrays``' shapes: ``{instruction name: (owner,
        "fwd" | "bwd" | None)}`` with the graph ops' names and
        ``optimizer`` / ``loss`` / ``metrics`` as owners
        (:func:`flexflow_tpu.obs.device_ops.table_from_hlo`).  Sum a
        profiler trace's operations by it with
        :func:`~flexflow_tpu.obs.device_ops.attribute`.  Lowers and
        compiles the step (a persistent-cache hit where the run's own
        compile wrote one) the FIRST time it is asked for a shape and
        keeps the table; nothing else calls it, so a run that never asks
        compiles nothing twice."""
        from .obs.device_ops import STEP_OWNERS, table_from_hlo
        batch = tuple(self._shard_batch(arrays))
        key = tuple((a.shape, str(a.dtype)) for a in batch)
        if key not in self._step_op_tables:
            text = self._train_step.lower(
                self._params, self._opt_state, batch,
                self._step).compile().as_text()
            self._step_op_tables[key] = table_from_hlo(
                text, [op.name for op in self.layers] + list(STEP_OWNERS))
        return self._step_op_tables[key]

    def attention_kernels(self, training: bool = True) -> Dict[str, int]:
        """How many attention ops lowered to which core the last time
        the training step (``training=False``: a forward-only program)
        was traced: ``{"owned", "library", "dense", "ring"}`` — the
        repo's Pallas flash kernel, jax's library kernel behind layout
        transposes, the einsum chain, ring attention
        (``MultiHeadAttention._attend`` notes its choice at trace time).
        All zeros before the first step is traced."""
        tally = dict.fromkeys(("owned", "library", "dense", "ring"), 0)
        for op in self.layers:
            core = getattr(op, "kernel_cores", {}).get(training)
            if core is not None:
                tally[core] += 1
        return tally

    def _check_accum_divisible(self, n: int, what: str) -> None:
        """Every entry point that feeds the jitted step validates its
        batch here — the scan reshape inside would otherwise fail with
        an opaque trace error."""
        accum = self.config.gradient_accumulation_steps
        if accum > 1 and n % accum:
            raise ValueError(
                f"{what} {n} does not divide into "
                f"gradient_accumulation_steps={accum} equal microbatches")

    def _surface_runtime_fallbacks(self) -> None:
        """Drain the sharding layer's aggregated replicate-fallback
        records (FF106) after a dispatch has executed (tracing done) —
        the trace-time truth the static compile pass could not see
        (e.g. ``verify="off"``, configs mutated after compile).  Called
        after train steps, AND after the first ``evaluate``/``predict``
        /serving dispatch — an inference-only session must see its
        fallbacks too, not just training runs.  Appends to
        ``verify_report``, accumulates the raw site tuples on
        ``self.runtime_fallback_sites`` (the set the static FF120
        prediction must equal — tests/test_sharding_passes.py pins it),
        and logs ONE aggregate line; cheap no-op when nothing fell
        back."""
        from .analysis.verifier import (drain_fallback_sites,
                                        fallback_site_diagnostics,
                                        has_fallback_records)
        if not has_fallback_records():
            return  # steady-state hot path (per serving dispatch):
            #         no set building, no global lock
        # drain only THIS model's sites: the recorder is process-global
        # and another model tracing in the same process must not have
        # its fallbacks absorbed (and mis-attributed) here.  Names are
        # the repo's one identity key (strategies, checkpoints, FF003)
        # — two models built with IDENTICAL op names are inherently
        # indistinguishable to the recorder, like everywhere else.
        cache = getattr(self, "_owned_names_cache", None)
        if cache is None or cache[0] != len(self.layers):
            owned = {t.name for op in self.layers for t in op.outputs}
            owned.update(w.name for op in self.layers
                         for w in op.weights)
            cache = (len(self.layers), owned)
            self._owned_names_cache = cache
        sites, dropped = drain_fallback_sites(owned_names=cache[1])
        if not sites and not dropped:
            return
        self.runtime_fallback_sites.update(sites)
        diags = fallback_site_diagnostics(sites, dropped, code="FF106")
        report = getattr(self, "verify_report", None)
        if report is not None:
            report.extend(diags)
        from .fflogger import get_logger
        get_logger("sharding").warning(
            f"{sum(d.count for d in diags)} replicate fallback(s) at "
            f"trace time across {len(diags)} site(s) [FF106] — the "
            f"executor replicated requested splits; see "
            f"model.verify_report / flexflow-tpu lint")

    def _maybe_reshard_fault(self, start: int, end: int) -> None:
        """Consume every pending ``grow_at_step``/``shrink_at_step``
        fault for the just-completed window ``(start, end]`` (no-op
        without FF_FAULT) — the reshards run HERE, between dispatches,
        exactly where a production scale event would land."""
        for req in faults.reshard_at_window(start, end):
            self._apply_fault_reshard(*req)

    def _stale_under_mesh(self, arrays) -> bool:
        """True when a staged jax array was placed under a mesh that is
        no longer the model's — a reshard() landed between its prefetch
        and its dispatch."""
        if self.mesh is None:
            return False
        cur = self.mesh.mesh
        for a in arrays:
            m = getattr(getattr(a, "sharding", None), "mesh", None)
            if m is not None and m != cur:
                return True
        return False

    def _replace_stale(self, arrays, window: bool = False):
        """Re-place prefetched arrays onto the CURRENT mesh when a
        reshard invalidated their staging (via host — a committed
        old-mesh array handed straight to jnp.asarray would stay
        committed).  Cheap attribute check when nothing changed."""
        if not self._stale_under_mesh(arrays):
            return arrays
        host = tuple(np.asarray(a) for a in jax.device_get(list(arrays)))
        return tuple(self._shard_window(host) if window
                     else self._shard_batch(host))

    def train_batch(self, *arrays) -> float:
        """One fused train step; returns loss."""
        self._check_not_quantized("train_batch")
        if arrays:
            self._check_accum_divisible(len(arrays[0]), "batch of")
        # both stretches of host work lie on the profiler's clock under
        # names of their own (no-ops while no profiler session is open)
        with jax.profiler.TraceAnnotation("train_batch.h2d"):
            batch = tuple(self._shard_batch(arrays))
        with jax.profiler.TraceAnnotation("train_batch.dispatch"):
            self._params, self._opt_state, loss, sums = self._train_step(
                self._params, self._opt_state, batch, self._step)
        if self._host_shardings:
            self._repin_host()
        self._surface_runtime_fallbacks()
        self._step += 1
        self._last_metric_sums = sums
        # deterministic fault injection (no-op unless FF_FAULT is set):
        # the elastic recovery matrix kills/hangs/slows real train loops
        faults.on_step(self._step)
        self._maybe_reshard_fault(self._step - 1, self._step)
        return loss

    def train_window(self, window, nvalid=None):
        """Dispatch ONE fused multi-step training window
        (``FFConfig.steps_per_dispatch``): ``window`` is a tuple of
        stacked ``(w, batch...)`` arrays (host or device); the whole
        w-step scan executes as a single donated jitted program — zero
        per-step host sync.  ``nvalid`` (int vector of shape ``(w,)``)
        selects the masked padded-tail step (pad_tail mode).

        Per-step Python work moves to window granularity with documented
        semantics: ``_repin_host`` runs once per dispatch, the step
        counter advances by ``w``, and fault injection fires at the
        window edge (``faults.on_window`` — kill/hang step indices round
        UP).  Returns device-resident ``(losses, metric_sums)`` stacked
        per step; fetch them only when host values are actually needed
        (fit() fetches once per epoch)."""
        assert self._compiled, "call compile() first"
        w = int(window[0].shape[0])
        self._check_accum_divisible(int(window[0].shape[1]),
                                    "window batch of")
        if any(not isinstance(a, jax.Array) for a in window):
            # host arrays get the window sharding; already-placed jax
            # arrays (PrefetchLoader.iter_windows staged them through
            # _shard_window) are trusted as-is — re-placing every
            # dispatch would put per-array host work back on the hot
            # path this fusion exists to amortize
            window = tuple(self._shard_window(window))
        else:
            # ...unless a reshard() changed the mesh after this window
            # was staged (cheap attribute check when nothing changed)
            window = self._replace_stale(window, window=True)
        start = self._step
        with jax.profiler.StepTraceAnnotation("train_window",
                                              step_num=start):
            if nvalid is None:
                self._params, self._opt_state, losses, sums = \
                    self._train_window(self._params, self._opt_state,
                                       window, start)
            else:
                nv = jnp.asarray(np.asarray(nvalid), jnp.int32)
                self._params, self._opt_state, losses, sums = \
                    self._train_window_masked(self._params,
                                              self._opt_state, window,
                                              start, nv)
        if self._host_shardings:
            self._repin_host()  # once per DISPATCH, not per step
        self._step += w
        self._last_metric_sums = sums
        faults.on_window(start, self._step)  # no-op without FF_FAULT
        self._maybe_reshard_fault(start, self._step)
        return losses, sums

    def fit(self, x, y, epochs: Optional[int] = None,
            batch_size: Optional[int] = None, callbacks=None,
            verbose: bool = True, validation_data=None, pad_tail=None):
        """Epoch loop (reference keras BaseModel.fit / alexnet.cc:102-118).
        Prints the reference's end-of-run throughput line
        (alexnet.cc:129-130).  ``validation_data=(x_val, y_val)`` runs a
        masked evaluate() after every epoch; val_loss and val_<metric>s
        join the JSON epoch event, the human line, and the
        ``PerfMetrics`` handed to callbacks (keras-style early stopping
        can watch them).

        ``config.steps_per_dispatch=K > 1`` fuses K train steps into ONE
        dispatched window (train_window): per-step host work — Python
        dispatch, ``_repin_host``, fault hooks — is paid once per window,
        losses/metric sums stay on device until the per-epoch fetch, and
        checkpoint/callback cadence (epoch boundaries) remains
        window-aligned by construction.  ``pad_tail`` (default:
        ``config.pad_tail_batches``) trains the tail samples that do not
        fill a batch via the masked padded step instead of dropping them;
        the THROUGHPUT line counts the samples actually trained either
        way.  Per-step losses of the last epoch are kept on
        ``self.last_epoch_losses`` (host, fetched with the epoch's
        metric sums)."""
        self._check_not_quantized("fit")
        cfg = self.config
        epochs = epochs or cfg.epochs
        bs = batch_size or cfg.batch_size
        self._check_accum_divisible(bs, "fit batch_size")
        k = max(1, int(cfg.steps_per_dispatch))
        pad = cfg.pad_tail_batches if pad_tail is None else bool(pad_tail)
        # K=1 without padding keeps the historical one-step dispatch loop
        # bit-exactly; windows engage for K>1 or padded-tail training
        use_windows = k > 1 or pad
        if validation_data is not None:
            if not isinstance(validation_data, (tuple, list)) \
                    or len(validation_data) != 2:
                raise ValueError(
                    "validation_data must be a (x_val, y_val) pair"
                    + ("; per-sample validation weights (the keras "
                       "3-tuple) are not supported"
                       if isinstance(validation_data, (tuple, list))
                       and len(validation_data) == 3 else ""))
        xs = x if isinstance(x, (list, tuple)) else [x]
        callbacks = callbacks or []
        for cb in callbacks:
            cb.set_model(self)
            cb.on_train_begin()
        if cfg.profiling:
            # --profiling: per-op fwd/bwd latency table (reference
            # conv_2d.cu:446-471 cudaEvent prints), measured in isolation
            from .profiling import profile_model
            profile_model(self)
        import contextlib
        tracer = (jax.profiler.trace(cfg.trace_dir) if cfg.trace_dir
                  else contextlib.nullcontext())
        # span tracing (docs/observability.md): one trace id per fit()
        # call; every dispatched window below records a `train_window`
        # span against it — the training-side siblings of the serving
        # request spans, on the same exportable timeline
        from .obs.trace import tracer_from_config
        span_tr = tracer_from_config(cfg)
        fit_trace = span_tr.new_trace() if span_tr.active else None
        from .data.dataloader import PrefetchLoader
        loader = PrefetchLoader(self, xs, y, batch_size=bs,
                                steps_per_dispatch=k, pad_tail=pad)
        t_start = time.time()
        total_samples = 0
        val_time = 0.0
        with tracer:
            for epoch in range(epochs):
                for cb in callbacks:
                    cb.on_epoch_begin(epoch)
                self.perf_metrics = metrics_mod.PerfMetrics()
                epoch_sums = []
                epoch_losses = []
                dispatches, dispatch_time = 0, 0.0
                epoch_step0 = self._step
                if use_windows:
                    # fused multi-step path: one host re-entry per K-step
                    # window; losses/sums stack on device inside the scan
                    for window, nvalid in loader.iter_windows():
                        t_d = time.perf_counter()
                        step0 = self._step
                        losses, sums = self.train_window(window, nvalid)
                        t_d1 = time.perf_counter()
                        dispatch_time += t_d1 - t_d
                        dispatches += 1
                        if fit_trace is not None:
                            span_tr.span(
                                "train_window", fit_trace, t_d, t_d1,
                                cat="train", tid="train", epoch=epoch,
                                step0=step0, steps=self._step - step0)
                        epoch_losses.append(losses)
                        epoch_sums.append(sums)
                else:
                    for batch in loader:
                        # a reshard() in the previous iteration (fault-
                        # injected or explicit) invalidates the already-
                        # prefetched batch's placement
                        batch = self._replace_stale(batch)
                        t_d = time.perf_counter()
                        with jax.profiler.StepTraceAnnotation(
                                "train", step_num=self._step):
                            self._params, self._opt_state, loss, sums = \
                                self._train_step(self._params,
                                                 self._opt_state,
                                                 batch, self._step)
                        if self._host_shardings:
                            self._repin_host()
                        dispatch_time += time.perf_counter() - t_d
                        dispatches += 1
                        self._step += 1
                        faults.on_step(self._step)  # no-op without FF_FAULT
                        self._maybe_reshard_fault(self._step - 1,
                                                  self._step)
                        # keep losses/metric sums on device; fetching here
                        # would fence the async dispatch pipeline every step
                        epoch_losses.append(loss)
                        epoch_sums.append(sums)
                total_samples += loader.num_samples_used
                self._surface_runtime_fallbacks()  # post-trace, per epoch
                fetched_sums, fetched_losses = jax.device_get(
                    (epoch_sums, epoch_losses))
                for sums in fetched_sums:
                    if use_windows:  # stacked (w,) per-step sums: fold
                        sums = {mk: v.sum(axis=0) for mk, v in sums.items()}
                    self.perf_metrics.update(sums)
                self.last_epoch_losses = (
                    np.concatenate([np.atleast_1d(l) for l in fetched_losses])
                    if fetched_losses else np.zeros((0,), np.float32))
                val_scalars: Dict[str, float] = {}
                if validation_data is not None:
                    xv, yv = validation_data
                    t_val0 = time.time()
                    val_loss, val_pm = self.evaluate(xv, yv, batch_size=bs)
                    # validation (incl. the one-time _eval_step compile)
                    # must not skew the reference-parity THROUGHPUT line
                    val_time += time.time() - t_val0
                    val_scalars = {"val_loss": float(val_loss)}
                    val_scalars.update(
                        {f"val_{k}": float(v)
                         for k, v in val_pm.scalars().items()
                         if k != "samples_seen"})
                    # callbacks watch these (keras-style early stopping)
                    self.perf_metrics.val_scalars = val_scalars
                # train-loop stats feed the process metrics registry
                # (docs/observability.md "Metrics"): the epoch event
                # below and a /metrics scrape report the same numbers
                from .obs.registry import get_registry
                _reg = get_registry()
                _reg.counter("ff_train_steps_total",
                             "Optimizer steps executed").labels().inc(
                    self._step - epoch_step0)
                _reg.counter("ff_train_dispatches_total",
                             "Training dispatches (fused windows count "
                             "once)").labels().inc(dispatches)
                _reg.counter("ff_train_samples_total",
                             "Training samples consumed").labels().inc(
                    loader.num_samples_used)
                _reg.gauge("ff_train_dispatch_ms",
                           "Mean wall ms per training dispatch, last "
                           "epoch").labels().set(
                    dispatch_time / max(1, dispatches) * 1e3)
                # structured per-epoch record (one parseable JSON line; the
                # reference only had printf metrics — SURVEY §5 observability)
                from .fflogger import get_logger
                get_logger("ff").event(
                    "epoch", epoch=epoch, step=self._step,
                    samples=total_samples,
                    elapsed_s=round(time.time() - t_start, 3),
                    # dispatch-fusion observability: host re-entries this
                    # epoch and mean wall time per dispatched window
                    # (docs/performance.md "Fused multi-step dispatch")
                    steps_per_dispatch=k,
                    dispatches=dispatches,
                    dispatch_ms=round(
                        dispatch_time / max(1, dispatches) * 1e3, 3),
                    **{mk: round(float(v), 6)
                       for mk, v in {**self.perf_metrics.scalars(),
                                     **val_scalars}.items()})
                for cb in callbacks:
                    cb.on_epoch_end(epoch, self.perf_metrics)
                stopping = any(getattr(cb, "stop_training", False)
                               for cb in callbacks)
                # -p/--print-freq gates the human line only (the JSON event
                # above records every epoch); first/last/stopping epochs
                # always print
                if verbose and (epoch % cfg.print_frequency == 0
                                or epoch == epochs - 1 or stopping):
                    line = (f"epoch {epoch}: "
                            f"{self.perf_metrics.report(self.metrics or [self.loss_type])}")
                    if val_scalars:
                        line += " — " + ", ".join(
                            f"{k}: {v:.6g}" for k, v in val_scalars.items())
                    print(line)
                if stopping:
                    break
            jax.block_until_ready(self._params)
        elapsed = time.time() - t_start
        train_elapsed = max(1e-9, elapsed - val_time)
        if verbose and elapsed > 0:
            # reference alexnet.cc:129-130 throughput line — TRAINING
            # time only (per-epoch validation is excluded)
            print(f"ELAPSED TIME = {train_elapsed:.4f}s, "
                  f"THROUGHPUT = {total_samples / train_elapsed:.2f} "
                  f"samples/s")
        for cb in callbacks:
            cb.on_train_end()
        return self.perf_metrics

    @staticmethod
    def _pad_tail(arrays, bs: int):
        """Zero-pad a ragged tail batch to the full batch size so the jitted
        step sees a static shape (and sharded batch dims stay divisible)."""
        out = []
        for a in arrays:
            a = np.asarray(a)
            short = bs - a.shape[0]
            if short > 0:
                a = np.concatenate(
                    [a, np.zeros((short,) + a.shape[1:], a.dtype)])
            out.append(a)
        return tuple(out)

    def evaluate(self, x, y, batch_size: Optional[int] = None):
        """Masked batched evaluation.  Per-batch loss/metric sums stay ON
        DEVICE through the loop and are fetched once at the end — a
        per-batch ``float()`` fetch would fence the async dispatch
        pipeline every batch, the exact anti-pattern fit() avoids
        (repo_lint RL004 locks this in)."""
        self._check_not_quantized("evaluate")
        bs = batch_size or self.config.batch_size
        xs = x if isinstance(x, (list, tuple)) else [x]
        n = xs[0].shape[0]
        pm = metrics_mod.PerfMetrics()
        device_sums = []
        total = 0
        for it in range(-(-n // bs)):
            lo, hi = it * bs, min(n, (it + 1) * bs)
            arrs = self._pad_tail(
                tuple(a[lo:hi] for a in xs) + (y[lo:hi],), bs)
            batch = tuple(self._shard_batch(arrs))
            _, bloss, sums = self._eval_step(self._params, batch, hi - lo)
            total += hi - lo
            device_sums.append((bloss, sums))
        fetched = jax.device_get(device_sums)  # ONE fetch for the loop
        # inference-only sessions trace here first: surface any
        # replicate fallbacks the eval trace recorded (ISSUE 9 — the
        # old train-step-only drain left evaluate()/predict() blind)
        self._surface_runtime_fallbacks()
        loss_sum = float(sum(b for b, _ in fetched))
        for _, sums in fetched:
            pm.update(sums)
        denom = max(1, total) if self._loss_reduction == "mean" else 1
        return loss_sum / denom, pm

    # ------------------------------------------------------------------
    # inference: shape-bucketed AOT executables (docs/serving.md)
    # ------------------------------------------------------------------
    def _dummy_label(self, bs: int) -> np.ndarray:
        """The zero label feed inference dispatches carry (the fused
        forward signature includes the label slot), cached per batch
        size — predict()/serving reuse it every call instead of
        re-allocating it per dispatch."""
        lab = self._dummy_labels.get(bs)
        if lab is None:
            lab = np.zeros((bs,) + tuple(self.label_tensor.shape[1:]),
                           self.label_tensor.dtype)
            self._dummy_labels[bs] = lab
        return lab

    def quantize_weights(self, mode: str = "int8") -> Dict[str, Any]:
        """Int8 weight-only quantization for serving (ISSUE 14,
        docs/serving.md "Int8 weight quantization"): every eligible
        matmul kernel (``serving.quantize.eligible_weights`` — the ONE
        eligibility predicate the fleet gate shares) is replaced IN
        ``self._params`` by a per-output-channel symmetric int8 tensor
        plus its f32 ``<name>::scale`` vector, placed under the weight's
        resolved sharding (scale replicated — it is (out,)-tiny).  The
        dequantization fuses into the matmul at trace time
        (``ops.common.dequant_matmul``), so the resident HBM footprint
        and the weight-streaming bandwidth drop to ~1/4 of f32 — the
        quantity the fleet gate's ``resident_bytes`` now predicts
        byte-for-byte.

        Returns the quality report: ``max_abs_err`` (measured, over all
        quantized weights), ``error_bound`` (max per-channel scale / 2 —
        the symmetric-rounding bound, which holds by construction), and
        per-weight rows.  The serving engine checks the bound at warmup
        and refuses to serve a violating table.

        One-way for this model instance: training/eval verbs and
        checkpointing refuse to run on quantized weights (build a fresh
        model to train).  Idempotent — a second call with the same mode
        returns the cached report."""
        assert self._compiled and self._params, \
            "compile() + init_layers() before quantize_weights()"
        if self._quantized:
            if self._quantized != mode:
                raise ValueError(
                    f"weights already quantized as {self._quantized!r}")
            return self._quant_report
        from .serving.quantize import quantize_params
        new_params, report = quantize_params(self, mode)
        self._params = new_params
        self._quantized = mode
        self._quant_report = report
        # the params' avals changed: every AOT bucket executable lowered
        # from the f32 params is stale, and the digest half of the cache
        # key must change with them
        self._fwd_compiled = {}
        self._exec_digest_cache = None
        from .fflogger import get_logger
        get_logger("serve").event(
            "quantize_weights", mode=mode,
            weights=len(report["weights"]),
            bytes_before=report["bytes_before"],
            bytes_after=report["bytes_after"],
            max_abs_err=report["max_abs_err"],
            error_bound=report["error_bound"])
        return report

    def _check_not_quantized(self, verb: str) -> None:
        if getattr(self, "_quantized", ""):
            raise RuntimeError(
                f"{verb}() is not available on a weight-quantized model "
                f"(quantize_weights({self._quantized!r}) is one-way for "
                f"this instance — serving-only); build and train a "
                f"fresh model")

    def exec_digest(self) -> str:
        """sha256/16 over everything a lowered forward executable
        depends on: the op graph (names, types, output shapes/dtypes),
        the resolved per-op strategies, the mesh factorization and the
        compute dtype.  Part of the bucket-executable cache key
        (:meth:`forward_compiled`), so in a multi-model process (a
        serving fleet — serving/fleet) an executable lowered for model
        A can never be handed to model B, and a graph/strategy change
        that goes through compile()/reshard() misses the cache instead
        of dispatching a stale program (tests/test_fleet.py pins the
        two-model collision case).  Cached per compile — recomputed
        whenever :meth:`_build_step_fns` rebuilds the programs, which
        is also where the executable cache itself resets."""
        cached = getattr(self, "_exec_digest_cache", None)
        if cached is not None:
            return cached
        import hashlib
        h = hashlib.sha256()
        for op in self.layers:
            h.update(op.name.encode())
            h.update(str(getattr(op, "op_type", "")).encode())
            for t in op.outputs:
                h.update(repr((tuple(t.shape), str(t.dtype))).encode())
            pc = op.parallel_config
            h.update(repr(None if pc is None else
                          (tuple(pc.dims), int(pc.device_type),
                           tuple(pc.device_ids),
                           getattr(pc, "precision", ""))).encode())
        if self.mesh is not None:
            h.update(repr(sorted(self.mesh.sizes.items())).encode())
        h.update(self.config.compute_dtype.encode())
        # precision keys the executable cache (ISSUE 14): an int8
        # weight-quantized program and its f32 twin must never share a
        # bucket entry (per-op precision rides in the pc tuples above)
        h.update(getattr(self, "_quantized", "").encode())
        self._exec_digest_cache = h.hexdigest()[:16]
        return self._exec_digest_cache

    def forward_compiled(self, bucket_bs: int):
        """The inference forward AOT-lowered and compiled at batch size
        ``bucket_bs`` (``jax.jit(...).lower(...).compile()``), cached
        per ``(bucket, exec_digest)`` — compile once at startup, then
        every dispatch of that shape reuses the executable with zero
        retrace/cache-lookup ambiguity.  The digest half of the key
        pins the executable to THIS model's graph + strategies + mesh
        (:meth:`exec_digest`): in a fleet process the per-model caches
        cannot cross, and a post-compile graph mutation misses instead
        of dispatching a stale program.  The serving engine warms one
        executable per shape bucket this way; ``predict()`` routes
        through the same cache.  Call as
        ``forward_compiled(bs)(model._params, batch)`` where ``batch``
        is ``(*inputs, dummy_label)`` shaped ``(bs, ...)`` and placed
        like :meth:`_shard_batch` places it (params are passed per
        call — pinned on device, never donated)."""
        assert self._compiled, "call compile() first"
        if int(bucket_bs) < 1:
            raise ValueError(f"bucket batch size must be >= 1, got "
                             f"{bucket_bs}")
        key = (int(bucket_bs), self.exec_digest())
        cached = self._fwd_compiled.get(key)
        if cached is not None:
            return cached
        specs = []
        for t in list(self.input_tensors) + [self.label_tensor]:
            shape = (int(bucket_bs),) + tuple(t.shape[1:])
            dtype = jnp.dtype(t.dtype)
            sharding = None
            if self.mesh is not None and self.mesh.is_distributed:
                entries = self._infer_batch_entries(shape, dtype)
                sharding = self.mesh.sharding(
                    jax.sharding.PartitionSpec(*entries))
            specs.append(jax.ShapeDtypeStruct(shape, dtype,
                                              sharding=sharding))
        compiled = self._jit_forward.lower(self._params,
                                           tuple(specs)).compile()
        self._fwd_compiled[key] = compiled
        return compiled

    # predict()'s device-side logit accumulation drains to host whenever
    # this many elements are pending (~256 MB of f32): typical calls get
    # ONE transfer at the end, while a huge-dataset x wide-head predict
    # keeps bounded device residency instead of stacking every batch's
    # logits in HBM until the loop ends
    _PREDICT_DRAIN_ELEMS = 1 << 26

    def predict(self, x, batch_size: Optional[int] = None) -> np.ndarray:
        """Batched inference through the bucket executable for
        ``batch_size`` (:meth:`forward_compiled` — compiled once,
        shared with the serving engine's AOT cache).  Per-batch logits
        stack up ON DEVICE and drain to host in bounded chunks (one
        transfer total for typical sizes) — the old per-batch
        ``np.asarray`` fenced the async pipeline every batch
        (repo_lint RL004)."""
        xs = x if isinstance(x, (list, tuple)) else [x]
        if len(xs) != len(self.input_tensors):
            raise ValueError(
                f"model has {len(self.input_tensors)} input(s), got "
                f"{len(xs)}")
        # coerce to the declared input dtypes up front: the AOT
        # executable is compiled for them (the old per-call jit would
        # silently retrace for an int feed to a float input; one cast
        # here keeps that working and matches ServingEngine.submit)
        xs = [np.asarray(a, dtype=t.dtype)
              for a, t in zip(xs, self.input_tensors)]
        n = xs[0].shape[0]
        bs = batch_size or self.config.batch_size
        dummy_label = self._dummy_label(bs)
        fwd = self.forward_compiled(bs)
        pending: List[jax.Array] = []
        host: List[np.ndarray] = []

        def drain():
            # amortized fetch: at most one fence per _PREDICT_DRAIN_ELEMS
            # pending elements, never one per batch
            host.extend(jax.device_get(pending))
            pending.clear()

        pending_elems = 0
        for it in range(-(-n // bs)):
            lo, hi = it * bs, min(n, (it + 1) * bs)
            arrs = tuple(a[lo:hi] for a in xs)
            if hi - lo < bs:  # exact batches skip the pad path entirely
                arrs = self._pad_tail(arrs, bs)
            batch = tuple(self._shard_infer_batch(arrs + (dummy_label,)))
            out = fwd(self._params, batch)
            pending.append(out)
            pending_elems += out.size
            if pending_elems >= self._PREDICT_DRAIN_ELEMS:
                drain()
                pending_elems = 0
        drain()
        # the AOT lowering above is a trace too: surface its replicate
        # fallbacks for inference-only sessions (ISSUE 9)
        self._surface_runtime_fallbacks()
        host = [o[:min(n - it * bs, bs)] for it, o in enumerate(host)]
        return np.concatenate(host, axis=0)

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def summary(self) -> str:
        lines = [f"{'op':30s} {'type':14s} {'output':24s} {'params':>12s}"]
        total = 0
        for op in self.layers:
            nparam = sum(w.volume for w in op.weights)
            total += nparam
            lines.append(f"{op.name:30s} {op.op_type.value:14s} "
                         f"{str(op.outputs[0].shape):24s} {nparam:12d}")
        lines.append(f"total parameters: {total}")
        return "\n".join(lines)

    @property
    def num_parameters(self) -> int:
        return sum(p.volume for p in self.parameters)
