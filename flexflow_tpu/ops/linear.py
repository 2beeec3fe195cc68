"""Linear / Embedding (reference ``src/ops/linear.cu``, ``src/ops/embedding.cu``).

Linear is the reference's tensor-parallel op: with ``num_par_c > 1`` it
replicates the input (linear.cu:168-207), computes partial input-grads into a
3-D replica tensor, and reduces them with a dedicated ``backward2_task``
saxpy pass (linear.cu:592-619).  TPU-native: the weight is sharded on the
output-channel dim over the "model" mesh axis; XLA's autodiff + GSPMD emit the
equivalent ``psum`` over ICI automatically — backward2 is gone by
construction.

Embedding shards its table over the out-dim (embedding.cu:95-103); the bwd
``atomicAdd`` scatter (embedding.cu:171-222) becomes the autodiff transpose
of ``take`` (a segment-sum XLA handles natively).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..config import DeviceType, MemoryType
from ..initializers import GlorotUniform, ZeroInitializer
from ..op import Op, OpContext, OpType
from .common import F32, apply_activation, cast_compute, dequant_matmul


def host_placed(pc) -> bool:
    """True when a ParallelConfig asks for host placement (reference
    hetero strategies: device_type CPU / memory ZCM, strategy.proto:11-18,
    dlrm_strategy_hetero.cc)."""
    return pc is not None and (pc.device_type == DeviceType.HOST
                               or MemoryType.ZCM in tuple(pc.memory_types))


def _host_gather(table, idx, mesh):
    """Gather on the HOST for a host-resident table: only the looked-up rows
    cross to HBM, never the table (the reference's CPU embedding task +
    zero-copy read path, embedding.cc:18-75, mapper.cc:66-71)."""
    from jax.experimental.compute_on import compute_on

    from ..compat import with_host_memory

    ds = NamedSharding(mesh.mesh, PartitionSpec())
    # feature-detected host memory kind (compat): backends without one
    # fall back to the plain device gather — correctness is unchanged,
    # only the table residency optimization is lost
    hs = with_host_memory(ds)
    if hs is None:
        return jnp.take(table, idx, axis=0)

    @compute_on("device_host")
    @jax.jit
    def gather(t, i):
        return t.at[i].get(mode="promise_in_bounds")

    y = gather(table, jax.device_put(idx, hs))
    return jax.device_put(y, ds)


class Linear(Op):
    op_type = OpType.LINEAR
    position_wise = True

    def __init__(self, name, input_tensor, out_dim, activation=None,
                 use_bias=True, kernel_initializer=None, bias_initializer=None):
        super().__init__(name, [input_tensor])
        in_dim = input_tensor.shape[-1]
        self.in_dim, self.out_dim = in_dim, out_dim
        self.activation = activation
        self.use_bias = use_bias
        out_shape = input_tensor.shape[:-1] + (out_dim,)
        self._add_output(out_shape, input_tensor.dtype)
        # (out, in) layout, matching reference create_linear_weight
        # (model.cc:582-669); sharded_dim=0 -> out-channel TP axis
        self.w_kernel = self._add_weight(
            (out_dim, in_dim), kernel_initializer or GlorotUniform(),
            "kernel", sharded_dim=0)
        if use_bias:
            self.w_bias = self._add_weight(
                (out_dim,), bias_initializer or ZeroInitializer(), "bias",
                sharded_dim=0)

    def forward(self, params, inputs, ctx: OpContext):
        x = cast_compute(inputs[0], ctx)
        k = params[self.w_kernel.name]
        if k.dtype == jnp.int8:
            # int8 weight-only serving path (FFModel.quantize_weights):
            # per-output-channel dequant fused into the matmul — the
            # resident weight is the int8 tensor, never an f32 copy
            from .common import scale_param_name
            y = dequant_matmul(x, k, params[scale_param_name(
                self.w_kernel.name)], "...i,oi->...o")
        else:
            y = jnp.einsum("...i,oi->...o", x, cast_compute(k, ctx),
                           preferred_element_type=jnp.float32)
        if self.use_bias:
            y = y + params[self.w_bias.name].astype(y.dtype)
        y = apply_activation(y, self.activation)
        return [cast_compute(y, ctx)]

    def parallel_dims(self):
        # sample dim + out-channel dim (reference TP axis, §2.15)
        nd = self.outputs[0].num_dims
        return (True,) * nd

    def flops(self):
        batch = self.outputs[0].volume // self.out_dim
        return 2 * batch * self.in_dim * self.out_dim

    def sub_problem(self, part_degrees):
        # a c split on the output shards the (out, in) kernel's rows; the
        # input is replicated at full feature width (linear.cu:168-207)
        from ..op import pad_degrees
        out = self.outputs[0]
        dims = pad_degrees(part_degrees, out.num_dims)
        c_deg = dims[-1]
        if self.out_dim % max(1, c_deg):
            raise ValueError(f"out_dim {self.out_dim} % c {c_deg}")
        x = self.inputs[0]
        in_shape = x.sub_shape(dims[:-1] + (1,))
        shapes = {self.w_kernel.name: (self.out_dim // max(1, c_deg),
                                       self.in_dim)}
        if self.use_bias:
            shapes[self.w_bias.name] = (self.out_dim // max(1, c_deg),)
        return [in_shape], shapes


class Embedding(Op):
    op_type = OpType.EMBEDDING
    position_wise = True

    def __init__(self, name, input_tensor, num_entries, out_dim,
                 aggr="sum", kernel_initializer=None):
        super().__init__(name, [input_tensor])
        self.num_entries, self.out_dim, self.aggr = num_entries, out_dim, aggr
        n = input_tensor.shape[0]
        if aggr in (None, "none"):
            # sequence mode (transformer token embedding): keep every
            # looked-up row — (n, s) ids -> (n, s, d)
            self.aggr = "none"
            self._add_output(input_tensor.shape + (out_dim,), F32)
        else:
            self._add_output((n, out_dim), F32)
        self.w_table = self._add_weight(
            (num_entries, out_dim), kernel_initializer or GlorotUniform(),
            "table", sharded_dim=1)

    def serve_check(self, max_seq):
        if self.aggr != "none":
            raise ValueError(
                f"{self.name}: only sequence-mode (aggr='none') "
                f"embeddings decode; bag aggregation collapses "
                f"the sequence dim")

    def forward(self, params, inputs, ctx: OpContext):
        idx = inputs[0].astype(jnp.int32)
        if ctx.embedding_rows and self.name in ctx.embedding_rows:
            # sparse-update path: the train step pre-gathered the rows
            # and differentiates w.r.t. THEM (the table never enters the
            # autodiff graph) — see FFConfig.sparse_embedding_updates
            y = ctx.embedding_rows[self.name]
        elif host_placed(self.parallel_config) and ctx.mesh is not None:
            table = params[self.w_table.name]
            y = _host_gather(table, idx, ctx.mesh)
        else:
            table = params[self.w_table.name]
            y = jnp.take(table, idx, axis=0)  # (n, [s,] d)
        if y.ndim == 3 and self.aggr != "none":  # bag of indices per sample
            if self.aggr == "sum":
                y = y.sum(axis=1)
            elif self.aggr == "avg":
                y = y.mean(axis=1)
            else:
                raise ValueError(f"unknown aggr {self.aggr!r}")
        return [cast_compute(y, ctx)]

    def parallel_dims(self):
        # every dim: sample (+sequence in "none" mode) + out-dim — the table
        # shards over the out-dim (reference embedding.cu:95-103 via
        # create_linear_weight)
        return (True,) * self.outputs[0].num_dims

    def flops(self):
        return self.outputs[0].volume

    def sub_problem(self, part_degrees):
        # the out-dim split shards the table's columns; the id input only
        # splits over batch/sequence degrees (embedding.cu:95-103)
        from ..op import pad_degrees
        out = self.outputs[0]
        dims = pad_degrees(part_degrees, out.num_dims)
        c_deg = dims[-1]
        if self.out_dim % max(1, c_deg):
            raise ValueError(f"out_dim {self.out_dim} % c {c_deg}")
        ids = self.inputs[0]
        if self.aggr == "none":  # (n, s) ids mirror the (n, s, d) output
            id_dims = dims[: ids.num_dims]
        else:  # (n, bag) ids: only the sample degree applies
            id_dims = (dims[0],) + (1,) * (ids.num_dims - 1)
        in_shape = ids.sub_shape(id_dims)
        return [in_shape], {self.w_table.name: (
            self.num_entries, self.out_dim // max(1, c_deg))}
