#!/usr/bin/env python3
"""Ask a started GenerationEngine whether any serving program copies its
KV pool: builds the causal LM of one benchmark configuration with the
slots of one traffic mix, warms the engine up as the benchmark does, and
prints ``engine.pool_copies()`` — for every compiled serving program the
``copy`` operations whose element count is a pool leaf's (0 everywhere is
the stored form holding; docs/observability.md "pool_copies") — and
``engine.stats()["decode_attention"]``, the decode core each attention op
got (``paged`` reads the pool in place).  With ``--owners`` also
``engine.program_op_tables()`` by program: how many instructions of each
compiled program each graph op (layers together) owns, under the program's
name as a profiler trace prints it — what an operator with such a trace sums
it by (docs/observability.md "program_op_tables"); the compile of a program
is shared with the copies' count.

    python3 scripts/pool_copies.py                      # the gpt1 serve cell
    python3 scripts/pool_copies.py --draft-layers 2     # speculative: + verify, draft
    python3 scripts/pool_copies.py --owners             # + instructions by owner

Runs on whatever backend jax finds (the chip where there is one: the
device is printed with the answer).  Exit code 1 if any program copies.
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def owners_summary(table):
    """``{"<owner less its layer number>[.<part>]": instructions}`` of one
    program's owner table, ``nobody`` for what no scope owns."""
    out = {}
    for owner, part in table.values():
        who = (re.sub(r"_\d+$", "", owner) if owner else "nobody") + (
            "." + part if part else "")
        out[who] = out.get(who, 0) + 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="perfbench/configs/gpt1.json")
    ap.add_argument("--traffic", default="perfbench/traffic/closed-128-chat.json")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="make the engine speculative, its draft the same "
                         "configuration cut to this many layers")
    ap.add_argument("--owners", action="store_true",
                    help="also print every program's instructions by the "
                         "graph op that owns them")
    args = ap.parse_args(argv)

    import jax

    import flexflow_tpu as ff
    from flexflow_tpu import fflogger
    from perfbench.families import postnorm_transformer as fam

    with open(args.config) as fh:
        config = json.load(fh)
    with open(args.traffic) as fh:
        traffic = json.load(fh)
    slots = int(traffic["slots"])
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "slots": slots}

    def build(layers=None):
        cfg = dict(config)
        if layers:
            cfg[cfg["keys"]["layers"]] = layers
        model = fam.build_serve(cfg, traffic)
        model.init_layers(seed=0)
        return model

    spec = ({"draft_model": build(args.draft_layers), "spec_gamma": 4}
            if args.draft_layers else {})
    with fflogger.silenced("serve"):
        with ff.GenerationEngine(build(), slots=slots, **spec) as engine:
            out["programs"] = engine.pool_copies()
            out["decode_attention"] = engine.stats()["decode_attention"]
            if args.owners:
                out["owners"] = {
                    name: owners_summary(table) for name, table
                    in engine.program_op_tables().items()}
    print(json.dumps(out, indent=1))
    return int(any(v["count"] for v in out["programs"].values()))


if __name__ == "__main__":
    sys.exit(main())
