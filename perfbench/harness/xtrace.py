"""Reduction of a jax profiler trace to what the per-layer metrics read.

What a v5e trace looks like (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<i>``, whose lines are ``Steps``, ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (the
operations of the TensorCore, serial on that line, named by their HLO text
``%name.N = ...``) and ``Async XLA Ops`` (copies and collectives in flight,
overlapping the former).  The host is the plane ``/host:CPU`` with one line
per thread; ``jax.profiler.TraceAnnotation`` spans appear there under their
names on the same clock as the device's.  Times are nanoseconds.

A trace is reduced to plain data first (:func:`load`), so a recorded one can
be kept as a small JSON file and reduced again by a CPU test.
"""

from __future__ import annotations

import glob
import json
import os
import re

from .stats import median, union_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS, MODULES = "XLA Ops", "XLA Modules"
# host spans worth keeping: the benchmark's own and the program's
HOST_SPANS = re.compile(r"^(pb\.|train|generate|gen-prefill)")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def op_name(event_name):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(name):
    """``fusion.12`` -> ``fusion``: the name without its instance number."""
    return re.sub(r"[.\d]+$", "", name)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path):
    """``{"devices": {i: {"ops": [...], "modules": [...]}}, "host": [...]}``
    with every event as ``[name, start_ns, duration_ns]``.  ``path`` is an
    ``.xplane.pb`` file or a ``.json`` file that :func:`save` wrote."""
    if path.endswith(".json"):
        with open(path) as f:
            doc = json.load(f)
        doc["devices"] = {int(k): v for k, v in doc["devices"].items()}
        return doc
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    doc = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS:
                    dev["ops"] = [[op_name(e.name), e.start_ns, e.duration_ns]
                                  for e in line.events]
                elif line.name == MODULES:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            doc["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                doc["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if HOST_SPANS.match(e.name)]
    return doc


def save(doc, path, first_ns=None, last_ns=None):
    """Write ``doc`` as JSON, optionally cut to ``[first_ns, last_ns]``:
    device events wholly inside are kept, host spans are clipped to it."""
    def cut(events):
        return [e for e in events
                if (first_ns is None or e[1] >= first_ns)
                and (last_ns is None or e[1] + e[2] <= last_ns)]

    def clip(events):
        lo = -float("inf") if first_ns is None else first_ns
        hi = float("inf") if last_ns is None else last_ns
        return [[n, max(s, lo), min(s + d, hi) - max(s, lo)]
                for n, s, d in events if s < hi and s + d > lo]

    out = {"devices": {str(i): {k: cut(v) for k, v in d.items()}
                       for i, d in doc["devices"].items()},
           "host": clip(doc["host"])}
    with open(path, "w") as f:
        json.dump(out, f, separators=(",", ":"))


# --------------------------------------------------------------------------
# the reductions
# --------------------------------------------------------------------------
def window(doc, span="pb.traced_window"):
    """(start_ns, end_ns) of the benchmark's traced-window span."""
    spans = [e for e in doc["host"] if e[0] == span]
    if not spans:
        return None
    return spans[0][1], spans[0][1] + spans[0][2]


def _clipped(events, win):
    lo, hi = win
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def busy(doc, win, devices=None):
    """Seconds in which an operation ran, averaged over ``devices`` (all the
    trace's chips when None), the window's seconds, and the first device's
    gaps as ``(start_ns, end_ns)``."""
    per_dev, gaps0 = [], []
    devices = sorted(doc["devices"]) if devices is None else devices
    for i in devices:
        b, gaps = union_ns(_clipped(doc["devices"][i]["ops"], win))
        per_dev.append(b)
        if i == devices[0]:
            iv = _clipped(doc["devices"][i]["ops"], win)
            first = min((s for s, _ in iv), default=win[1])
            last = max((e for _, e in iv), default=win[0])
            gaps0 = [(win[0], first)] + gaps + [(last, win[1])]
    if not per_dev:
        return 0.0, (win[1] - win[0]) / 1e9, []
    return (sum(per_dev) / len(per_dev) / 1e9, (win[1] - win[0]) / 1e9,
            [g for g in gaps0 if g[1] > g[0]])


def module_times_ms(doc, prefix, device=None):
    """Device durations (ms) of the programs whose name starts ``prefix``."""
    device = min(doc["devices"]) if device is None else device
    return [d / 1e6 for n, _, d in doc["devices"][device]["modules"]
            if n.startswith(prefix)]


def module_span(doc, prefix, device=None):
    """(first start, last end) of the whole programs named ``prefix...``: an
    operation of a program that was already running when the trace began has
    no program event and falls outside."""
    device = min(doc["devices"]) if device is None else device
    ms = [(s, s + d) for n, s, d in doc["devices"][device]["modules"]
          if n.startswith(prefix)]
    if not ms:
        return None
    return min(s for s, _ in ms), max(e for _, e in ms)


def _ops_in(doc, span, device):
    device = min(doc["devices"]) if device is None else device
    ops = doc["devices"][device]["ops"]
    if span is None:
        return ops
    return [e for e in ops if e[1] >= span[0] and e[1] + e[2] <= span[1]]


def op_seconds(doc, pattern, span=None, device=None):
    """Summed device seconds of the operations whose NAME matches."""
    rx = re.compile(pattern)
    return sum(d for n, _, d in _ops_in(doc, span, device)
               if rx.search(n)) / 1e9


def busy_seconds(doc, span=None, device=None):
    b, _ = union_ns([(s, s + d) for _, s, d in _ops_in(doc, span, device)])
    return b / 1e9


def exposed_collective_seconds(doc, device=None):
    """Time device 0 spends in collective operations while nothing else runs
    there.  The ``XLA Ops`` line is serial, so a collective on it (a
    synchronous one, or the ``-done`` that waits for an asynchronous one)
    holds the core for its whole duration; what overlaps compute sits on the
    ``Async XLA Ops`` line and is not counted."""
    device = min(doc["devices"]) if device is None else device
    ops = doc["devices"][device]["ops"]
    coll = [(s, s + d) for n, s, d in ops if COLLECTIVE.match(n)]
    other = [(s, s + d) for n, s, d in ops if not COLLECTIVE.match(n)]
    both, _ = union_ns(coll + other)
    alone, _ = union_ns(other)
    return (both - alone) / 1e9


def top_ops(doc, n=10, device=None):
    """The operation kinds that took most device time: ``[[kind, s], ...]``."""
    device = min(doc["devices"]) if device is None else device
    tot = {}
    for name, _, d in doc["devices"][device]["ops"]:
        k = op_kind(name)
        tot[k] = tot.get(k, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(doc, gaps, n=10):
    """Device 0's idle gaps summed by what the host was doing at the middle
    of each: the innermost host span that covers it."""
    host = sorted(doc["host"], key=lambda e: e[2])   # shortest first
    tot = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = next((h[0] for h in host if h[1] <= mid <= h[1] + h[2]
                     and h[0] != "pb.traced_window"), "host:unattributed")
        tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def step_ms(doc, prefix):
    return median(module_times_ms(doc, prefix))
