"""Per-op profiling (the ``--profiling`` flag — reference cudaEvent timing
inside every forward/backward task, conv_2d.cu:446-471, linear.cu:379-406).

XLA fuses the whole step into one program, so a host clock cannot time one
op of the fused execution; like the reference's simulator measure mode
(``measure_compute_time``, simulator.cc:235-273), each op is compiled and
timed IN ISOLATION on the real device, fwd and fwd+bwd, then reported as a
table.  ``FFModel.fit`` prints it once up front when ``config.profiling``
is set.  For numbers read off the fused step itself (a profiler trace's
device operations summed by the graph op whose scope they were traced
under) see ``obs/device_ops.py`` and ``FFModel.step_op_table``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .op import Op, OpContext, resolve_conv_layout


class _NoFloatLeaf(ValueError):
    """The op has no float leaf to chain the timing loop on — a distinct
    type so profile_op's nan-degrade cannot mask genuine ValueErrors
    raised while tracing/executing the op's forward."""


def _example_inputs(op: Op, shapes=None, seed: int = 0):
    """Random float inputs (zeros/ones can flatter ops with data-dependent
    timing — ADVICE r3 on measure mode); int inputs stay zeros (always a
    valid index).  ``shapes`` overrides the declared shapes (measure mode's
    per-partition sub-shapes)."""
    rng = np.random.default_rng(seed)
    outs = []
    for i, t in enumerate(op.inputs):
        shape = tuple(shapes[i]) if shapes is not None else t.shape
        if t.dtype.startswith("int"):
            outs.append(jnp.zeros(shape, jnp.dtype(t.dtype)))
        else:
            outs.append(jnp.asarray(rng.standard_normal(shape),
                                    jnp.dtype(t.dtype)))
    return outs


def _init_params(op: Op, seed: int = 0, shapes=None) -> Dict[str, jax.Array]:
    from .initializers import GlorotUniform
    key = jax.random.PRNGKey(seed)
    params = {}
    for i, p in enumerate(op.weights):
        init = p.initializer or GlorotUniform()
        shape = tuple(shapes.get(p.name, p.shape)) if shapes else p.shape
        params[p.name] = init(jax.random.fold_in(key, i), shape,
                              jnp.dtype(p.dtype))
    return params


def profile_op(op: Op, compute_dtype: str = "bfloat16", warmup: int = 2,
               iters: int = 5, flash_attention=None, input_shapes=None,
               weight_shapes=None, conv_layout: str = "auto"
               ) -> Dict[str, float]:
    """(fwd_ms, bwd_ms) for one op, timed in isolation (reference
    measure_compute_time contract: returns per-config latency).  The ctx
    mirrors the run's kernel choices (flash_attention, conv_layout) so the
    numbers match what fit() actually executes.  ``input_shapes``/
    ``weight_shapes`` override the declared shapes — the simulator's
    measure mode times one PARTITION of the op this way (Op.sub_problem)."""
    # resolve "auto" against this op alone: a single op is never
    # concat-heavy, so isolated profiling defaults to NCHW — callers that
    # know the run's graph (Simulator.measure via optimize_strategies,
    # model_bottleneck.py) pass the RESOLVED layout instead
    ctx = OpContext(training=True, rng=jax.random.PRNGKey(0),
                    compute_dtype=compute_dtype,
                    flash_attention=flash_attention,
                    conv_layout=resolve_conv_layout(conv_layout, [op]))
    params = _init_params(op, shapes=weight_shapes)
    inputs = _example_inputs(op, shapes=input_shapes)

    def fwd(params, inputs):
        return op.forward(params, inputs, ctx)[0]

    float_in = [i for i, t in enumerate(op.inputs)
                if not t.dtype.startswith("int")]

    def fwd_bwd(params, inputs):
        def loss(params, *flt):
            full = list(inputs)
            for i, v in zip(float_in, flt):
                full[i] = v
            outs = op.forward(params, full, ctx)
            return sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in outs
                       if jnp.issubdtype(o.dtype, jnp.floating))
        # wgrad AND dgrad, matching the reference's separate
        # bwdFilter/bwdData measurement (conv_2d.cu:935-1037)
        argnums = (0,) + tuple(range(1, 1 + len(float_in)))
        return jax.grad(loss, argnums=argnums)(
            params, *[inputs[i] for i in float_in])

    try:
        fwd_ms = _time_loop(fwd, params, inputs, warmup, iters)
    except _NoFloatLeaf:
        # int-only inputs and no float weights (e.g. a reshape/split over
        # token ids): no float leaf to chain the timing loop on — report
        # nan instead of crashing the whole profile table (ADVICE r3 #2)
        return {"fwd_ms": float("nan"), "bwd_ms": float("nan")}
    try:
        tot_ms = (_time_loop(fwd_bwd, params, inputs, warmup, iters)
                  if (params or float_in) else fwd_ms)
    except Exception:
        tot_ms = float("nan")  # non-differentiable op (e.g. int gather only)
    # NaN must survive: max(0.0, nan - fwd) silently yields 0.0 in Python,
    # which misreports a failed backward as a free one
    bwd_ms = float("nan") if tot_ms != tot_ms else max(0.0, tot_ms - fwd_ms)
    return {"fwd_ms": fwd_ms, "bwd_ms": bwd_ms}


def quantiles(samples, qs=(0.5, 0.95, 0.99)) -> Dict[float, float]:
    """Nearest-rank quantiles of a sample sequence — the p50/p95/p99
    latency accounting of the serving metrics
    (flexflow_tpu/serving/metrics.py).  Nearest-rank
    (not interpolated): every reported value is a latency that actually
    happened, which is what a tail-latency SLO compares against.
    Returns ``{q: value}``; empty input yields NaNs."""
    xs = sorted(samples)
    if not xs:
        return {q: float("nan") for q in qs}
    n = len(xs)
    return {q: float(xs[min(n - 1, _nearest_rank(q, n))]) for q in qs}


def _nearest_rank(q: float, n: int) -> int:
    """0-based nearest-rank index: ceil(q*n) - 1, computed in exact
    integer arithmetic for the common x.xx quantiles so float jitter
    (0.95*20 == 18.999...96) cannot shift the rank."""
    num = int(round(q * 10000))
    return max(0, -(-num * n // 10000) - 1)


def time_calls(fn, min_time_s: float = 0.3, max_calls: int = 1_000_000
               ) -> Tuple[float, int]:
    """(calls/sec, n_calls) of repeatedly invoking ``fn()`` until at
    least ``min_time_s`` of wall clock accumulates.  Host-side CPU
    timing of search throughput — the simulator runs on the host, so no
    device fence is involved."""
    import time as _time
    n = 0
    t0 = _time.perf_counter()
    while True:
        fn()
        n += 1
        dt = _time.perf_counter() - t0
        if dt >= min_time_s or n >= max_calls:
            return n / dt, n


def _time_loop(fn_core, params, inputs, warmup: int, iters: int) -> float:
    """Per-execution ms of ``fn_core(params, inputs)``, measured as the
    two-point slope of an IN-PROGRAM ``fori_loop``.

    A single op runs for microseconds, the same order as one host
    dispatch, so a host-side repeat loop would measure the dispatch.
    Running N iterations inside one jitted fori_loop makes one dispatch
    cover N executions; timing N and 3N (each ended by
    ``block_until_ready``) and taking the slope cancels the remaining
    constant term exactly.  A
    loop-carried epsilon (scaled from the previous iteration's output)
    multiplies the smallest float leaf, so iterations form a true data
    chain XLA cannot hoist, at the cost of one elementwise pass over
    that leaf (the smallest one, so the overhead is negligible next to
    the op itself).
    """
    # The perturbed leaf must sit on the op's MULTIPLICATIVE path: chaining
    # through a bias leaves the conv/matmul loop-invariant and XLA hoists
    # it out of the loop (measured: conv collapses to ~1us).  Candidates
    # are inputs and >=2-D weights (kernels, tables); pick the smallest so
    # the per-iteration elementwise pass over it stays negligible.
    cands = [("input", i, t) for i, t in enumerate(inputs)
             if jnp.issubdtype(t.dtype, jnp.floating)]
    cands += [("param", k, v) for k, v in params.items()
              if jnp.issubdtype(v.dtype, jnp.floating) and v.ndim >= 2]
    if not cands:  # last resort: any float leaf (bias-only ops)
        cands = [("param", k, v) for k, v in params.items()
                 if jnp.issubdtype(v.dtype, jnp.floating)]
    if not cands:  # int-only op with no float weights: nothing to chain on
        raise _NoFloatLeaf("no float leaf to chain the timing loop on")
    kind, key, _ = min(cands, key=lambda c: c[2].size)
    target = (kind, key)

    # n is a TRACED fori_loop trip count (lowered to a while loop), so
    # the whole measurement uses ONE compile per fn regardless of how
    # many window sizes get probed.
    @jax.jit
    def run(params, inputs, n):
        def body(_, carry):
            eps, acc = carry
            p, inp = dict(params), list(inputs)
            kind, k = target
            if kind == "param":
                p[k] = p[k] * (1 + eps).astype(p[k].dtype)
            else:
                inp[k] = inp[k] * (1 + eps).astype(inp[k].dtype)
            out = fn_core(p, inp)
            # chain through a FULL reduction of every float leaf:
            # a single-element chain lets XLA narrow the program to
            # what that element needs — grads get DCE'd and slices
            # propagate INTO convs (measured: conv bwd collapses to
            # one output pixel).  A sum cannot be narrowed; it costs
            # one extra read pass per leaf, small next to the op.
            s = sum(jnp.sum(o.astype(jnp.float32))
                    for o in jax.tree_util.tree_leaves(out)
                    if jnp.issubdtype(o.dtype, jnp.floating))
            return s * jnp.float32(1e-30), acc + s
        _, acc = jax.lax.fori_loop(
            0, n, body, (jnp.float32(0), jnp.float32(0)))
        return acc

    def _timed(n):
        t0 = time.perf_counter()
        jax.block_until_ready(run(params, inputs, n))
        return time.perf_counter() - t0

    # the 3N leg must outlast host jitter: rescale below until it
    # covers this many seconds
    window = 0.01

    def _slope(n):
        for _ in range(max(1, warmup)):
            _timed(n)
        # on a loaded host jitter can outlast the op and turn the slope
        # negative: measure again (at most three times) before settling
        # for 0
        for _ in range(3):
            est = (_timed(3 * n) - _timed(n)) / (2 * n)
            if est > 0:
                return est
        return 0.0

    n = max(8, iters)
    est = _slope(n)
    if est * n < window / 5:  # window too small vs jitter: rescale
        n = int(min(4096, max(n, window / max(est, 1e-5))))
        est = _slope(n)
    return est * 1e3


def profile_model(model, file=None) -> List[Dict[str, float]]:
    """Print the reference's per-op timing table for every layer."""
    rows = []
    print(f"{'op':30s} {'type':14s} {'fwd(ms)':>9s} {'bwd(ms)':>9s}",
          file=file)
    for op in model.layers:
        r = profile_op(op, model.config.compute_dtype,
                       flash_attention=model.config.flash_attention,
                       conv_layout=model.config.conv_layout)
        rows.append({"name": op.name, **r})
        print(f"{op.name:30s} {op.op_type.value:14s} "
              f"{r['fwd_ms']:9.3f} {r['bwd_ms']:9.3f}", file=file)
    return rows
