"""Traffic kind ``train_steps``: optimizer steps dispatched back to back.

Set-up builds ONE object, the compiled step with its state, hands it the
seed's weights, drives it through its first three steps on the window's own
call (``train_batch``) and feed, and gives that same object to the window.
The window dispatches steps until ``--seconds`` have passed, keeping a
bounded number in flight (it waits on the loss of two steps back), and ends
with ``block_until_ready`` on the last loss.  After the window the plain
reference follows the same three steps from the same seed and the comparison
decides ``correct``; its time is no part of ``setup_s``.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from perfbench.harness.runner import peak_bytes

IN_FLIGHT = 2


MINORITY_EVERY = 8


def batch_labels(b, num_labels):
    """The labels of one batch before the seed places them: one row in
    ``MINORITY_EVERY`` carries a label other than 0 (the others in turn), the
    same counts in every batch of every seed: an imbalanced classification
    job, as fine-tuning jobs mostly are.  Not half and half: rows of random
    tokens pull the seeded weights nearly one way each, so with as many rows
    of either label the first gradient is the little that is left of two
    halves that cancel (0.08-0.47 of its mass on the chip, by the seed), Adam's
    first step is the sign of that remainder, and the three compared steps
    turn chaotic: 2 of 23 sound seeds read a loss 0.28 and a parameter change
    26 % off the reference's (my chip runs, PR 23).  At one in eight the
    gradient keeps 0.46-0.87 of its mass and every number is steady."""
    labels = np.zeros(b, np.int32)
    minority = np.arange(0, b, MINORITY_EVERY)
    labels[minority] = 1 + np.arange(len(minority)) % (num_labels - 1)
    return labels


def make_feed(traffic, sz, seed):
    """``host_batches`` seeded batches of distinct rows, cycled; which rows
    carry which of :func:`batch_labels` is the seed's."""
    rng = np.random.default_rng([int(seed), 1])
    b, s = int(traffic["global_batch"]), int(traffic["seq_len"])
    labels = batch_labels(b, sz["num_labels"])
    return [(rng.integers(0, sz["vocab"], (b, s)).astype(np.int32),
             rng.permutation(labels).reshape(b, 1))
            for _ in range(int(traffic["host_batches"]))]


def _norms(tree):
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _samples(tree, positions):
    import jax.numpy as jnp

    return {k: jnp.take(v.astype(jnp.float32).reshape(-1), positions[k])
            for k, v in tree.items()}


def first_steps(model, feed, steps, beta1, theta0, to_ref_layout, positions):
    """Drive the compiled step through its first ``steps`` steps and read
    what the comparison needs: every loss, the first gradient's per-leaf norm
    as the optimizer got it (Adam's first moment after one step is
    ``(1 - beta1) * g``), and the per-leaf norm of the parameters' change.
    ``theta0()`` gives the seed's weights again, placed like the model's.
    A seeded sample of the first gradient's elements (``positions``, in the
    reference's layout) is kept too: a gap between norms hardly moves under
    rounding, a difference of elements does."""
    import jax

    losses, grad, sample = [], None, None
    for i in range(steps):
        losses.append(model.train_batch(*feed[i % len(feed)]))
        if i == 0:
            grad = jax.jit(_norms)(model._opt_state["m"])
            sample = jax.jit(lambda m: _samples(to_ref_layout(m), positions))(
                model._opt_state["m"])
    start = theta0()
    delta = jax.jit(lambda a, b: _norms(
        {k: a[k] - b[k] for k in a}))(model._params, start)
    del start
    return {"losses": [float(x) for x in losses],
            "grad_norms": {k: float(v) / (1.0 - beta1)
                           for k, v in grad.items()},
            "grad_samples": {k: np.asarray(v, np.float64) / (1.0 - beta1)
                             for k, v in sample.items()},
            "delta_norms": {k: float(v) for k, v in delta.items()}}


def compare(prog, ref, leaf_index, limits):
    """The numbers compared, each with its limit.  Norms are taken by the
    worst leaf: the gap between the program's norm and the reference's,
    against the reference's figure for that leaf or for the median leaf,
    whichever is larger (some gradients are all but zero).  For the
    parameters' change that figure is the reference's norm; for the first
    gradient it is the reference's MASS (the mean over its micro-batches, one
    label each, of the norm of each one's gradient): with mixed labels the
    batch gradient is what is left of rows that cancel, by a margin that
    swings with the seed, and an arithmetic's error follows the rows."""
    numbers = [(f"loss_gap_step{i + 1}", abs(p - r), limits["loss_gap"])
               for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))]
    for what, against in (("grad_norms", "grad_mass_norms"),
                          ("delta_norms", "delta_norms")):
        def of(tree):
            return {name: float(tree[key] if layer is None
                                else tree[key][layer])
                    for name, (key, layer) in leaf_index.items()}

        ref_of, scale = of(ref[what]), of(ref[against])
        floor = float(np.median(list(scale.values())))
        worst = max(abs(prog[what][n] - r) / max(scale[n], floor)
                    for n, r in ref_of.items())
        numbers.append((f"{what[:-1]}_worst_leaf", worst,
                        limits[what[:-1] + "_worst_leaf"]))
    # the sampled elements of the first gradient, all leaves together: the
    # norm of the difference over the sample's mass
    diff = 0.0
    for name, (key, layer) in leaf_index.items():
        r = np.asarray(ref["grad_samples"][key] if layer is None
                       else ref["grad_samples"][key][layer], np.float64)
        diff += float(np.sum((prog["grad_samples"][name] - r) ** 2))
    numbers.append(("grad_sample_rel_diff",
                    diff ** 0.5 / ref["grad_sample_mass"],
                    limits["grad_sample_rel_diff"]))
    return numbers


def cancellation(ref, leaf_index):
    """For the record: the norm of the reference's sampled first gradient
    over its mass (1 where all rows pull one way, near 0 where they cancel)."""
    ref2 = sum(float(np.sum(np.asarray(
        ref["grad_samples"][key] if layer is None
        else ref["grad_samples"][key][layer], np.float64) ** 2))
        for key, layer in leaf_index.values())
    return ref2 ** 0.5 / ref["grad_sample_mass"]


def seed_first_steps(cell, fam, ref, model, seed):
    """Hand ``seed``'s weights to the compiled model and drive it through
    the compared steps on that seed's feed; returns what was read and the
    feed.  (``tests/perfbench/pb_control.py`` reads a dozen seeds through
    one compiled model with it.)"""
    tr, sz = cell.traffic, fam.sizes(cell.config)
    fam.install(model, sz, ref.init_params(sz, seed))
    feed = make_feed(tr, sz, seed)
    index = fam.leaf_index(sz)
    shapes = ref.param_shapes(sz)
    positions = {n: ref.sample_positions(shapes[k], layer is not None)
                 for n, (k, layer) in index.items()}
    prog = first_steps(
        model, feed, int(tr["compared_steps"]), float(tr["adam"]["beta1"]),
        lambda: fam.placed(model, sz, ref.init_params(sz, seed)),
        lambda m: fam.in_reference_layout(sz, m), positions)
    return prog, feed


def run(ctx, devs):
    import jax

    cell, tr = ctx.cell, ctx.cell.traffic
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    sz = fam.sizes(cell.config)
    ctx.mark("imports")
    model = fam.build_train(cell.config, tr, ctx.counters)
    ctx.mark("model built and compile() done")
    prog, feed = seed_first_steps(cell, fam, ref, model, ctx.seed)
    steps, index = int(tr["compared_steps"]), fam.leaf_index(sz)
    ctx.mark("weights installed, first steps read")
    for i in range(int(tr["warmup_steps"])):
        loss = model.train_batch(*feed[(steps + i) % len(feed)])
    jax.block_until_ready(loss)
    tokens = int(tr["global_batch"]) * int(tr["seq_len"])
    ctx.counters.update(tokens_per_step=tokens, step_program="jit_train_step")

    # ---- the window ----------------------------------------------------
    trace_at = float(tr["trace_after_s"]) if ctx.trace else None
    trace_left = int(tr["trace_steps"])
    tracing = False
    losses, pending = [], collections.deque()
    t0 = ctx.open_window()
    n = 0
    while time.perf_counter() - t0 < ctx.seconds:
        if trace_at is not None and not tracing and \
                time.perf_counter() - t0 >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation("pb.traced_window")
            span.__enter__()
            tracing, trace_at = True, None
        with jax.profiler.TraceAnnotation("pb.train.dispatch"):
            loss = model.train_batch(*feed[n % len(feed)])
        losses.append(loss)
        pending.append(loss)
        n += 1
        if len(pending) > IN_FLIGHT:
            with jax.profiler.TraceAnnotation("pb.train.wait_two_back"):
                jax.block_until_ready(pending.popleft())
        if tracing:
            trace_left -= 1
            if trace_left == 0:
                with jax.profiler.TraceAnnotation("pb.train.wait_last"):
                    jax.block_until_ready(loss)
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
    jax.block_until_ready(loss)
    t1 = time.perf_counter()
    if tracing or (ctx.trace and trace_left > 0):
        raise SystemExit("perfbench: --seconds too short for the traced "
                         "window of this traffic mix")
    peak = peak_bytes(devs)
    values = np.asarray([float(x) for x in losses])
    failed = int(np.sum(~np.isfinite(values)))
    rate = n * tokens / (t1 - t0)
    ctx.say(f"window {t1 - t0:.4f} s, {n} optimizer steps x {tokens} tokens "
            f"= {rate:.1f} tokens/s on {cell.chips} chip(s); non-finite "
            f"losses {failed}; last loss {values[-1]:.5f}")
    if ctx.trace:
        ctx.counters["sim_step_s"] = fam.simulated_step_s(model)

    # ---- the comparison, outside the window and outside set-up ---------
    del model
    t_ref = time.perf_counter()
    want = ref.train_steps(sz, ctx.seed, feed[:steps], dict(tr["adam"]),
                           int(tr["reference_micro_batch"]))
    numbers = compare(prog, want, index, cell.doc["limits"])
    ctx.say(f"reference: {steps} steps in {time.perf_counter() - t_ref:.2f} s"
            f" (not in setup_s); program losses {prog['losses']} reference "
            f"{want['losses']}; the first gradient's norm is "
            f"{cancellation(want, index):.3f} of its mass")
    return {"end_to_end": {"train_tokens_per_s": rate}, "attempted": n,
            "failed": failed, "numbers": numbers, "spans": [],
            "memory_peak_bytes": peak}
