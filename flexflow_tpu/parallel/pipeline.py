"""Pipeline parallelism — GPipe-style collective pipeline over the ``p``
mesh axis.

The reference has NO stage-based pipeline (SURVEY §2.15: per-op
``device_ids`` + Legion async task issue give only *implicit* overlap; the
NMT engine chunks timesteps the same way).  This module goes beyond it with
an explicit TPU-native pipeline: homogeneous stages hold their stacked
weights sharded over ``p`` (one stage per p-rank), microbatches stream
through a ``lax.scan`` of ticks, activations hop stage-to-stage with
``lax.ppermute``, and the final stage's emissions are psum-gathered.
Gradients fall out of autodiff through the scan (ppermute and psum are
linear), giving synchronous GPipe semantics: all microbatch gradients
accumulate before the update — no staleness.

Schedules:

* ``"gpipe"`` (default): tick t runs stage s on microbatch ``t - s``; a
  rank holding v stacked stages runs its whole group per tick, so a step
  costs ``(S + M - 1) * v`` stage-times — bubble fraction (S-1)/(S+M-1).
* ``"interleaved"`` (Megatron-style virtual stages): each rank holds v
  round-robin chunks (global stage t lives on rank ``t % S``) and runs ONE
  stage per tick; activations carry a (chunk, microbatch) tag around a
  ppermute ring with wraparound, and rank 0 injects a fresh microbatch
  whenever the wrap slot is empty.  The tick count is computed exactly by
  a static dataflow simulation — ~``v*M + S + v`` stage-times, cutting the
  bubble by ~v versus gpipe.  Traversal order is round-robin by
  construction; the p==1 fallback applies stages in the same order so
  numerics match the pipelined run exactly.

Gradients for both schedules come from autodiff through the scan
(ppermute/psum/dynamic_index are linear; their transposes reverse the
schedule), so there is no hand-written backward.

Why no 1F1B (VERDICT r3 #6 "consider 1F1B"): 1F1B's advantage over GPipe
is peak-activation memory — it caps in-flight microbatches at S by
running each microbatch's backward as soon as its forward clears the
last stage, which requires hand-interleaving fwd and bwd ticks in one
schedule and therefore a hand-written backward (autodiff cannot reverse
an interleaved schedule; the transpose of a scan is a scan in strict
reverse order).  Under XLA the same memory cap is reached compositionally:
``cfg.remat`` wraps stage forwards in ``jax.checkpoint`` (activations of
non-live microbatches are recomputed, not stored) and the interleaved
schedule already shrinks the bubble ~v-fold, while keeping gradients
autodiff-derived (provably consistent with the p==1 fallback — the
parity tests pin this).  Hand-scheduling 1F1B would trade that proof and
XLA's fusion freedom for memory we can already trade with remat.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from .mesh import MachineMesh


def traversal_order(total_stages: int, S: int, schedule: str):
    """Storage-index visit order of the pipeline.  gpipe visits the stage
    dim in storage order; interleaved visits round-robin over ranks
    (traversal step t -> storage index (t % S) * v + t // S, i.e. rank
    t % S, local chunk t // S under contiguous p-sharding)."""
    if schedule != "interleaved" or S <= 1:
        return list(range(total_stages))
    v = total_stages // S
    return [(t % S) * v + t // S for t in range(total_stages)]


def _interleaved_ticks(S: int, M: int, v: int) -> int:
    """Exact tick count of the interleaved dataflow (static Python
    simulation of the tag protocol — the same priority rule the traced
    tick uses: an arriving wrapped unit beats a pending injection)."""
    arriving = [None] * S  # unit at each rank's input: (mb, chunk)
    inj = done = t = 0
    while done < M:
        nxt = [None] * S
        for r in range(S):
            unit = arriving[r]
            if r == 0 and unit is None and inj < M:
                unit = (inj, 0)
                inj += 1
            if unit is None:
                continue
            mb, c = unit
            if r == S - 1:
                if c == v - 1:
                    done += 1  # final stage of final chunk -> output
                else:
                    nxt[0] = (mb, c + 1)  # wrap to rank 0, next chunk
            else:
                nxt[r + 1] = (mb, c)
        arriving = nxt
        t += 1
    return t


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: MachineMesh,
                   num_microbatches: Optional[int] = None,
                   schedule: str = "gpipe",
                   virtual_stages: Optional[int] = None):
    """Run the stacked stages over ``x`` as a collective pipeline.
    Returns ``(y, aux)`` — aux is the per-batch sum of the stages'
    auxiliary losses (0 when stage_fn returns a bare array).

    stage_fn(params, x) -> y [or (y, aux_scalar)] with y.shape == x.shape
    (shape-homogeneous stages; the stage BODY is arbitrary — see
    ops/pipeline.PipelineSegment for stages built from any FFModel
    subgraph, including MoE);
    ``stacked_params``: pytree whose leaves carry a leading stage dim,
    sharded over the mesh's ``p`` axis.  x: (n, ...) activations; returns
    same-shaped y.  ``schedule``: "gpipe" or "interleaved"; the latter
    REQUIRES ``virtual_stages`` (chunks per rank), which pins the
    traversal order mesh-independently — the p==1 fallback then
    reproduces the pipelined numerics exactly.

    Only the ``p`` sub-axes are MANUAL in the shard_map — every other
    mesh axis stays auto, so activations keep their ``n`` (data) sharding
    and stage bodies may carry ``c`` (tensor) and ``e`` (expert) sharding
    constraints inside: GSPMD inserts the TP/MoE collectives within each
    pipeline rank.  This is what composes {n, c, e, p} in one program.
    """
    assert schedule in ("gpipe", "interleaved"), schedule
    leaves = jax.tree.leaves(stacked_params)
    total_stages = leaves[0].shape[0]
    for leaf in leaves:
        assert leaf.shape[0] == total_stages, \
            "all stacked leaves must share the stage dim"

    def sfn(params, h):  # normalize: stages may or may not emit aux
        r = stage_fn(params, h)
        return r if isinstance(r, tuple) else (r, jnp.float32(0.0))

    if schedule == "interleaved":
        if not virtual_stages or total_stages % virtual_stages != 0:
            raise ValueError(
                f"interleaved schedule needs virtual_stages dividing "
                f"num_stages={total_stages}, got {virtual_stages}")
        S_eff = total_stages // virtual_stages  # required pipeline width
    S = mesh.axis_size("p")
    if S <= 1:
        # no pipeline axis: same math in the schedule's traversal order
        order = traversal_order(total_stages,
                                S_eff if schedule == "interleaved" else 1,
                                schedule)
        ordered = jax.tree.map(lambda a: a[jnp.asarray(order)],
                               stacked_params) if order != list(
            range(total_stages)) else stacked_params

        def body(h, params):
            y, aux = sfn(params, h)
            return y, aux

        y, auxs = lax.scan(body, x, ordered)
        return y, jnp.sum(auxs)

    if total_stages % S != 0:
        raise ValueError(
            f"num_stages={total_stages} must be a multiple of the mesh 'p' "
            f"axis size {S} (each rank runs a group of stages)")
    if schedule == "interleaved" and S != S_eff:
        raise ValueError(
            f"interleaved schedule with virtual_stages={virtual_stages} "
            f"needs mesh p == {S_eff}, got {S}")
    M = num_microbatches or S
    p_axes = mesh.subaxes("p")
    # activations enter with their data (n) sharding intact on the AUTO
    # axes; only the stage dim of the weights is a manual (p) spec
    x_spec = PartitionSpec(*([None] * x.ndim))
    pspec = jax.tree.map(
        lambda a: PartitionSpec(p_axes, *([None] * (a.ndim - 1))),
        stacked_params)

    if schedule == "interleaved":
        v = virtual_stages
        fn = partial(_pipeline_interleaved_local, stage_fn=sfn, S=S,
                     M=M, v=v, p_axes=p_axes,
                     ticks=_interleaved_ticks(S, M, v))
    else:
        fn = partial(_pipeline_local, stage_fn=sfn, S=S, M=M,
                     p_axes=p_axes)
    # rank identity rides in as a p-sharded operand instead of
    # lax.axis_index, which under a partial-auto shard_map lowers to a
    # PartitionId instruction the SPMD partitioner has rejected when
    # auto axes are present; the aux accumulator crosses the boundary
    # as shape (1,) because out_specs need a dim to name
    rank_ids = jnp.arange(S, dtype=jnp.int32)
    y, aux = jax.shard_map(
        fn, mesh=mesh.mesh,
        in_specs=(pspec, x_spec, PartitionSpec(p_axes)),
        out_specs=(x_spec, PartitionSpec(None)), check_vma=False,
        axis_names=frozenset(p_axes))(stacked_params, x, rank_ids)
    return y, aux[0]


def _pipeline_interleaved_local(stacked_local, x_loc, rank_arr, *,
                                stage_fn, S: int, M: int, v: int, p_axes,
                                ticks: int):
    """Per-rank interleaved (virtual-stage) loop.  This rank holds v
    chunks; local chunk c is global stage ``c*S + rank``.  Each activation
    rides the full ring carrying (chunk, microbatch) tags; rank S-1 wraps
    non-final chunks back to rank 0, which otherwise injects fresh
    microbatches.  One stage-application per rank per tick.
    ``rank_arr`` is this rank's (1,) slice of the p-sharded arange —
    the portable axis_index (see pipeline_apply)."""
    idx = rank_arr[0]
    n_loc = x_loc.shape[0]
    assert n_loc % M == 0, (n_loc, M)
    xm = x_loc.reshape((M, n_loc // M) + x_loc.shape[1:])
    ring = [(j, (j + 1) % S) for j in range(S)]

    x0 = jnp.zeros_like(xm[0])
    tag0 = jnp.asarray(-1, jnp.int32)   # chunk of the arriving unit; -1=idle
    mb0 = jnp.asarray(0, jnp.int32)
    inj0 = jnp.asarray(0, jnp.int32)    # next microbatch to inject (rank 0)
    out0 = jnp.zeros_like(xm)
    # (1,)-shaped, never 0-d: see pipeline_apply's out_specs note
    aux0 = jnp.zeros((1,), jnp.float32)

    def tick(carry, _):
        x_arr, tag, mb, inj, out, aux = carry
        can_inject = (idx == 0) & (tag < 0) & (inj < M)
        x_in = jnp.where(can_inject, xm[jnp.clip(inj, 0, M - 1)], x_arr)
        tag = jnp.where(can_inject, 0, tag)
        mb = jnp.where(can_inject, inj, mb)
        inj = inj + can_inject.astype(inj.dtype)
        chunk_params = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(
                a, jnp.clip(tag, 0, v - 1), 0, keepdims=False),
            stacked_local)
        y, a = stage_fn(chunk_params, x_in)
        y = y.astype(x_in.dtype)
        y = jnp.where(tag >= 0, y, x_in)    # idle tick: pass-through mask
        aux = aux + jnp.where(tag >= 0, a, 0.0)  # idle ticks chew garbage
        is_final = (idx == S - 1) & (tag == v - 1)
        emitted = out.at[jnp.clip(mb, 0, M - 1)].set(y)
        out = jnp.where(is_final & (tag >= 0), emitted, out)
        # chunk advances on the wrap past the last rank; final chunks leave
        # the ring as an empty slot rank 0 can fill
        send_tag = jnp.where(
            tag < 0, -1,
            jnp.where(idx == S - 1,
                      jnp.where(tag == v - 1, -1, tag + 1), tag))
        x_nxt = lax.ppermute(y, p_axes, ring)
        tag_nxt = lax.ppermute(send_tag, p_axes, ring)
        mb_nxt = lax.ppermute(mb, p_axes, ring)
        return (x_nxt, tag_nxt, mb_nxt, inj, out, aux), None

    (_, _, _, _, out, aux), _ = lax.scan(
        tick, (x0, tag0, mb0, inj0, out0, aux0), jnp.arange(ticks))
    out = lax.psum(jnp.where(idx == S - 1, out, jnp.zeros_like(out)), p_axes)
    # /M rescales the M per-microbatch aux terms to the p==1 fallback's
    # full-batch scale.  EXACT only for batch-linear aux (plain means);
    # nonlinear statistics like MoE's sum_e f_e*P_e load-balance loss
    # differ from the full-batch value by O(microbatch variance) — parity
    # tests against p==1 need a tolerance, not exactness.
    aux = lax.psum(aux, p_axes) / M
    return out.reshape(x_loc.shape), aux


def _pipeline_local(stacked_local, x_loc, rank_arr, *, stage_fn, S: int,
                    M: int, p_axes):
    """Per-device GPipe loop (runs inside shard_map).  Each rank holds a
    contiguous GROUP of stages (total_stages / S per rank, often 1) and
    applies them in order within its tick.  ``rank_arr`` is this rank's
    (1,) slice of the p-sharded arange (portable axis_index)."""
    idx = rank_arr[0]
    n_loc = x_loc.shape[0]
    assert n_loc % M == 0, (n_loc, M)
    xm = x_loc.reshape((M, n_loc // M) + x_loc.shape[1:])
    state0 = jnp.zeros_like(xm[0])
    out0 = jnp.zeros_like(xm)
    # activations hop s -> s+1; rank 0 has no upstream (it injects)
    perm = [(j, j + 1) for j in range(S - 1)]

    def run_group(x_in):
        # scan this rank's local stage group in order
        def body(h, params):
            y, a = stage_fn(params, h)
            return y.astype(h.dtype), a

        y, auxs = lax.scan(body, x_in, stacked_local)
        return y, jnp.sum(auxs)

    def tick(carry, t):
        state, out, aux = carry
        mb_in = xm[jnp.clip(t, 0, M - 1)]
        x_in = jnp.where(idx == 0, mb_in, state)
        y, a = run_group(x_in)
        y = y.astype(state.dtype)
        # this rank computes real data only at ticks idx <= t < idx + M;
        # bubble ticks chew zeros whose aux must not count
        aux = aux + jnp.where((t >= idx) & (t < idx + M), a, 0.0)
        m = t - (S - 1)  # microbatch the LAST stage just finished
        emitted = out.at[jnp.clip(m, 0, M - 1)].set(y)
        valid = (idx == S - 1) & (m >= 0)
        out = jnp.where(valid, emitted, out)
        state = lax.ppermute(y, p_axes, perm)
        return (state, out, aux), None

    # (1,)-shaped aux carry, never 0-d: see pipeline_apply's note
    (state, out, aux), _ = lax.scan(
        tick, (state0, out0, jnp.zeros((1,), jnp.float32)),
        jnp.arange(S + M - 1))
    # only the last rank holds real outputs; broadcast around the ring
    out = lax.psum(jnp.where(idx == S - 1, out, jnp.zeros_like(out)), p_axes)
    # /M rescales per-microbatch aux to full-batch scale (exact only for
    # batch-linear aux — see the interleaved loop's note)
    aux = lax.psum(aux, p_axes) / M
    return out.reshape(x_loc.shape), aux
