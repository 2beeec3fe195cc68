"""The traced window's serving programs' device time summed by the graph op
that owns it, for the per-layer metrics that read it (``serve_unowned_share``,
``attention_share.serve``): the twin of ``step_owners.py``.

The driver drops its engine and its model before the readers run, so the
cell's graph is built again through the family, WITHOUT weights and without a
pool: an engine that is never started gives the geometry, its decoder builds
the programs the engine's warm-up builds (every chunk bucket and the token
step), and the program maps every instruction of each compiled program to its
owner (``GenerationEngine.program_op_tables``, from the scopes the programs
were traced under; the persistent compilation cache answers the compiles).
Once per run: the first reader that asks pays for it, the others find it on
``obs``.
"""

from __future__ import annotations

import bisect
import re
import time

from perfbench.harness.stats import median

# the programs of a step boundary, by the name the ``XLA Modules`` line
# prints less its fingerprint (``jit_prefill`` alone: every bucket of a
# program from before the names)
KINDS = (("chunk", re.compile(r"^jit_prefill(_\d+)?$")),
         ("token", re.compile(r"^jit_decode(_s)?$")))


def program_kind(name):
    """``chunk``, ``token`` or ``other`` (verify, draft, the token splice)."""
    return next((kind for kind, rx in KINDS if rx.match(name)), "other")


def read(obs):
    """:func:`reduce` of the run's trace by the cell's own owner tables, or
    None where there is no device trace (a CPU rehearsal pays nothing)."""
    if not hasattr(obs, "_serve_owners"):
        obs._serve_owners = (
            None if obs.trace is None or obs.window is None
            else reduce(obs.trace, obs.window, tables(obs.cell), obs.xtrace))
    return obs._serve_owners


def tables(cell):
    """``{program name: {instruction: (owner, part)}}`` of the programs the
    cell's engine serves with; ``{}`` for a program that has no such counter
    (one from before the scopes, which the benchmark also runs: every second
    inside its programs is then nobody's)."""
    import flexflow_tpu as ff
    from flexflow_tpu import fflogger
    from flexflow_tpu.serving.generation.decoder import GraphDecoder

    if not hasattr(GraphDecoder, "program_op_tables"):
        print(f"[{cell.name}] the program has no owner tables "
              "(GraphDecoder.program_op_tables)", flush=True)
        return {}
    t0 = time.perf_counter()
    tr = dict(cell.traffic)     # as the driver's traced run builds it
    tr["program_args"] = list(tr["program_args"]) + list(
        tr["program_args_traced"])
    fam = cell.module("families", cell.config["family"])
    model = fam.build_serve(cell.config, tr)
    with fflogger.silenced("serve"):
        engine = ff.GenerationEngine(
            model, slots=int(tr["slots"]),
            max_new_tokens=int(tr["new_tokens"]["max"]))
    # the decoder of that geometry IS the engine's (one a geometry a model)
    dec = GraphDecoder.for_model(
        model, engine.slots, engine.max_seq, page_size=engine.page_size,
        num_pages=engine.num_pages, prefill_chunk=engine.prefill_chunk)
    for bucket in dec.buckets:
        dec.prefill_fn(bucket)
    dec.decode_fn()
    try:
        got = engine.program_op_tables()
    except RuntimeError as e:   # a cached executable from before the scopes
        raise SystemExit(f"perfbench: {e}") from None
    print(f"[{cell.name}] owner tables of {len(got)} programs "
          f"({sum(map(len, got.values()))} instructions) in "
          f"{time.perf_counter() - t0:.2f} s, after the window", flush=True)
    return got


def reduce(doc, window, tables, xtrace):
    """Device 0's operations inside the program events (``XLA Modules``)
    that lie wholly inside ``window``, each given to its event's program and,
    by that program's table, to an owner: ``{"seconds": {(program kind, owner
    kind, part): s}, "by_kind": {operation kind: {"<program kind>:<owner
    kind>[.<part>]": s}}, "programs": {name: [events, median ms]},
    "unowned": s, "total": s}``.  An owner's kind is its name less its layer
    number (``attention``, ``moe``, ``lm_head``, ``sample``), ``nobody``
    where the table names none or has no such instruction or program.  An
    operation outside every such event counts nowhere."""
    dev = doc["devices"][min(doc["devices"])]
    lo, hi = window
    events = sorted((s, s + d, n.split("(", 1)[0])
                    for n, s, d in dev["modules"] if s >= lo and s + d <= hi)
    starts = [e[0] for e in events]
    ns, by_kind = {}, {}
    for name, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s + d > events[i][1]:
            continue
        program = events[i][2]
        owner, part = tables.get(program, {}).get(name, (None, None))
        kind = program_kind(program)
        who = re.sub(r"_\d+$", "", owner) if owner else "nobody"
        ns[kind, who, part] = ns.get((kind, who, part), 0) + d
        ops = by_kind.setdefault(xtrace.op_kind(name), {})
        label = f"{kind}:{who}" + (f".{part}" if part else "")
        ops[label] = ops.get(label, 0.0) + d / 1e9
    programs = {}
    for s, e, program in events:
        programs.setdefault(program, []).append((e - s) / 1e6)
    # summed in nanoseconds, so the parts add up to the whole exactly
    return {"seconds": {key: v / 1e9 for key, v in ns.items()},
            "by_kind": by_kind,
            "programs": {p: [len(ms), median(ms)]
                         for p, ms in programs.items()},
            "unowned": sum(v for (_, who, _), v in ns.items()
                           if who == "nobody") / 1e9,
            "total": sum(ns.values()) / 1e9}
