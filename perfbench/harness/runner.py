"""One run of one cell: load, warm, measure, compare, print one last line."""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import cells, xtrace
from .compilestats import CompileStats

CACHE_DIR = ".jax_cache_chip"   # inside the checkout, a fixed path


@dataclasses.dataclass
class Context:
    cell: cells.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                 # process start on time.perf_counter
    compile_stats: CompileStats
    counters: dict                 # what the benchmark counts on the way
    trace_dir: str = ""
    t_window: float = 0.0

    def open_window(self):
        """Everything before this instant is set-up."""
        self.counters.update(self.compile_stats.snapshot())
        self.mark("window opens")
        self.t_window = time.perf_counter()
        return self.t_window

    def mark(self, what):
        """A line with the seconds since the process started."""
        self.say(f"+{time.perf_counter() - self.t_start:.2f} s {what}")

    def say(self, text):
        print(f"[{self.cell.name}] {text}", flush=True)


@dataclasses.dataclass
class Observed:
    """What a per-layer metric's reader may read."""
    cell: cells.Cell
    counters: dict
    spans: list          # the program's own spans (obs.trace), traced runs
    trace: dict | None   # the reduced profiler trace, traced runs
    window: tuple | None
    peaks: dict
    sizes: dict
    flops: object        # perfbench/flops/<family>.py
    xtrace: object = xtrace


def _devices(cell, require_tpu):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise cells.BenchmarkError(
            f"no accelerator: jax found platform {devs[0].platform!r} "
            f"({devs[0].device_kind}); nothing was run")
    if len(devs) < cell.chips:
        raise cells.BenchmarkError(
            f"cell {cell.name} needs {cell.chips} chips, jax sees "
            f"{len(devs)}; nothing was run")
    return devs


def _place_cache(root):
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, CACHE_DIR))
    # every program of a run goes to the cache, the short compiles too, so
    # that a cell's second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peak_bytes(devs):
    """Peak device memory on the fullest chip.  The TPU runtime keeps two
    disjoint accounts (looked at on a v5e, PR 23): ``peak_bytes_in_use`` is
    the buffers the process holds (weights, optimizer state, KV pool,
    batches) and ``peak_bytes_reserved`` the arena a running program's
    temporaries live in (``memory_analysis().temp_size_in_bytes``: 8.6 GB of
    activations for a BERT-base step at batch 32).  The peak is their sum; a
    backend without the second account reports the first alone."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def decide(ctx, numbers):
    """Print each number compared beside its limit; all must hold."""
    ok = True
    for name, value, limit in numbers:
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok &= good
        ctx.say(f"compare {name}: {value!r} (limit {limit!r}) "
                f"{'ok' if good else 'NOT CORRECT'}")
    return ok and bool(numbers)


def run_cell(root, workload, seed, seconds, trace, t_start,
             require_tpu=True):
    """Returns the result object (the last line's content)."""
    cell = cells.load(root, workload)
    import jax

    _place_cache(root)
    devs = _devices(cell, require_tpu)
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), t_start=t_start,
                  compile_stats=CompileStats(),
                  counters={"chips": cell.chips})
    ctx.say(f"platform={devs[0].platform} device_kind={devs[0].device_kind!r} "
            f"count={len(devs)} jax={jax.__version__} seed={ctx.seed} "
            f"seconds={ctx.seconds} trace={int(ctx.trace)} config="
            f"{cell.config_name} traffic={cell.traffic_name} "
            f"cache={jax.config.jax_compilation_cache_dir}")
    if ctx.trace:
        ctx.trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        driver = cell.module("drivers", cell.traffic["kind"])
        out = driver.run(ctx, devs[:cell.chips])
        trace_doc = win = None
        if ctx.trace:
            path = xtrace.find_xplane(ctx.trace_dir)
            if path is None:
                raise cells.BenchmarkError("the profiler wrote no trace")
            trace_doc = xtrace.load(path)
            win = xtrace.window(trace_doc)
            if win is None:
                raise cells.BenchmarkError(
                    "the trace lacks the pb.traced_window span")
            if not trace_doc["devices"]:
                if require_tpu:
                    raise cells.BenchmarkError(
                        "the trace holds no /device:TPU plane")
                trace_doc = None    # a CPU rehearsal: spans and counters only
    finally:
        if ctx.trace_dir:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    setup_s = ctx.t_window - ctx.t_start
    ctx.say(f"setup_s {setup_s:.3f} (compile_s "
            f"{ctx.counters.get('compile_s', 0.0):.3f}, cache misses "
            f"{ctx.counters.get('cache_misses')}, hits "
            f"{ctx.counters.get('cache_hits')}); memory_peak_bytes "
            f"{out['memory_peak_bytes']}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": decide(ctx, out["numbers"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if not ctx.trace:
        values = dict(out["end_to_end"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is None or not math.isfinite(v):
                raise cells.BenchmarkError(
                    f"cell {cell.name} did not measure {m['name']}: {v!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        family = cell.module("families", cell.config["family"])
        obs = Observed(cell=cell, counters=ctx.counters, spans=out["spans"],
                       trace=trace_doc, window=win,
                       peaks=cell.peaks(devs[0].device_kind)
                       if require_tpu else {},
                       sizes=family.sizes(cell.config),
                       flops=cell.module("flops", family.FLOPS))
        metrics = {}
        for m in cell.per_layer:
            value = cell.module("layer_metrics", m["name"]).read(obs)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif require_tpu:
                # BENCHMARK.json lists the metric for this cell, so on the
                # chip its reader has something to read or something broke
                raise cells.BenchmarkError(
                    f"cell {cell.name} lists per-layer metric {m['name']} "
                    f"and its reader found nothing to read: {value!r}")
        if trace_doc is not None:
            busy_s, window_s, gaps = xtrace.busy(trace_doc, win)
            device.update(busy_s=busy_s, window_s=window_s)
            result["breakdown"] = {
                "device_ops": xtrace.top_ops(trace_doc),
                "idle_gaps": xtrace.idle_gaps(trace_doc, gaps)}
    # each number compared beside its limit comes last in the line
    result.update(metrics=metrics, device=device,
                  compared=[list(n) for n in out["numbers"]])
    return result


def main(argv, t_start, root=None):
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    result = run_cell(root, args.workload, args.seed, args.seconds,
                      args.trace, t_start)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    # and as the last lines of standard error
    for name, value, limit in result["compared"]:
        print(f"compare {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    return 0
