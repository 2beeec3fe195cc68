"""``flexflow-tpu`` console entry — the reference's ``flexflow_python``
runner (python/Makefile, flexflow_top.py:164-220): parses the FlexFlow flag
set into an FFConfig, installs it as the process default, and executes the
user script.

    flexflow-tpu my_model.py -b 64 -e 10 --lr 0.01 -ll:tpu 8 --budget 500

Where the reference launches the script as a Legion top-level task, here the
script simply runs under CPython with ``FFConfig.parse_args``'s result made
available via :func:`flexflow_tpu.get_default_config` (scripts may also call
``FFConfig.parse_args()`` themselves, same flags)."""

from __future__ import annotations

import os
import runpy
import sys

from .config import FFConfig


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    # built-in subcommands (no user script involved)
    if argv and argv[0] == "calibrate":
        # harvest measured op/dispatch timings into a CalibrationTable,
        # or --check existing artifacts (docs/strategy_search.md)
        from .search.calibration import calibrate_main
        raise SystemExit(calibrate_main(argv[1:]))
    if argv and argv[0] == "elastic":
        # supervised multi-process training with restart-from-checkpoint
        # (docs/elastic.md)
        raise SystemExit(elastic_main(argv[1:]))
    if argv and argv[0] == "lint":
        # static strategy/graph verifier (docs/verifier.md)
        raise SystemExit(lint_main(argv[1:]))
    if argv and argv[0] == "explain":
        # device-free sharding/communication/memory report for a
        # strategy on a mesh you may not own yet (docs/verifier.md)
        raise SystemExit(explain_main(argv[1:]))
    if argv and argv[0] == "trace":
        # export/inspect recorded request-span traces
        # (docs/observability.md)
        from .obs.trace import trace_main
        raise SystemExit(trace_main(argv[1:]))
    if argv and argv[0] == "flight":
        # flight-recorder post-mortem dumps (docs/observability.md)
        from .obs.flight import flight_main
        raise SystemExit(flight_main(argv[1:]))
    run_script(argv)


def run_script(argv) -> dict:
    """``flexflow-tpu <script.py> [FlexFlow flags]``: parse the flags
    into the process default FFConfig and execute the script as
    ``__main__``.  Returns the script's module namespace, so an
    embedding caller (chip_smoke.py) can inspect the model the script
    trained; :func:`main` discards it."""
    script = None
    for a in argv:
        if a.endswith(".py"):
            script = a
            break
    if script is None:
        print("usage: flexflow-tpu <script.py> [FlexFlow flags]\n"
              "       flexflow-tpu elastic [supervisor flags] -- "
              "<script.py> [script args]\n"
              "       flexflow-tpu calibrate [--out table.json | "
              "--check FILE...]\n"
              "       flexflow-tpu lint --model NAME [--strategy s.pb] "
              "[--devices N] [--json]\n"
              "       flexflow-tpu lint --fleet fleet.json "
              "[--hbm-gb G] [--json]\n"
              "       flexflow-tpu explain --model NAME [--strategy "
              "s.pb] [--mesh n=4,c=2] [--json]\n"
              "       flexflow-tpu explain --fleet fleet.json [--json]\n"
              "       flexflow-tpu trace export RAW.json [--out f.json]\n"
              "       flexflow-tpu flight dump|show [--dir D]\n"
              "flags (reference model.cc:1221-1289): -e -b --lr --wd -d "
              "--budget --alpha --search-mode --best-known "
              "--reshard-budget -s/-import -ll:tpu "
              "-ll:cpu --nodes --profiling --seed --remat "
              "--steps-per-dispatch --pad-tail --calibration "
              "--cost-estimator "
              "--serve-max-batch --serve-max-wait-ms --serve-buckets "
              "--serve-max-queue-rows --serve-admission "
              "--serve-starvation-ms --trace-sample-rate --metrics-port",
              file=sys.stderr)
        raise SystemExit(2)
    flags = [a for a in argv if a != script]
    cfg = FFConfig.parse_args(flags)
    import flexflow_tpu
    flexflow_tpu.set_default_config(cfg)
    # observability plane (docs/observability.md): a fatal uncaught
    # exception in the user script dumps the flight ring before the
    # traceback prints; --metrics-port exposes the process registry
    from .obs.flight import install_excepthook
    install_excepthook()
    if cfg.metrics_port > 0:
        from .obs.registry import start_metrics_server
        server = start_metrics_server(cfg.metrics_port,
                                      host=cfg.metrics_host)
        print(f"[obs] metrics on {cfg.metrics_host}:"
              f"{server.server_port}/metrics", file=sys.stderr)
    # bring up the multi-host runtime when this is one process of a slice
    # (single-process runs are a no-op) — the reference's GASNet bring-up
    # happens likewise before the top-level task runs.  --nodes > 1 makes
    # the multi-host requirement explicit: failing to form the world is an
    # error, not N disconnected replicas.
    from flexflow_tpu.parallel import initialize_distributed
    initialize_distributed(
        num_processes=cfg.num_nodes if cfg.num_nodes > 1 else None)
    # a training run keeps its compiles like the engines do
    # (flexflow_tpu/compile_cache.py: the one placement rule)
    from .compile_cache import enable as enable_compile_cache
    enable_compile_cache()
    # the script sees the remaining argv like any __main__
    sys.argv = [script] + flags
    return runpy.run_path(script, run_name="__main__")


def _lint_builders():
    """Builtin-model registry for ``lint``: name -> zero-config builder
    returning an FFModel.  Lazy imports keep ``lint --help`` fast."""
    from .models import (build_alexnet, build_candle_uno, build_dlrm,
                         build_inception_v3, build_nmt, build_resnet50,
                         build_transformer)
    return {
        "transformer": lambda cfg: build_transformer(cfg)[0],
        # 8 tables make the default interact width (8*64+64) match
        # mlp_top[0]=576 (the reference run-script shape)
        "dlrm": lambda cfg: build_dlrm(
            cfg, embedding_size=(1000000,) * 8)[0],
        "alexnet": lambda cfg: build_alexnet(cfg)[0],
        "resnet": lambda cfg: build_resnet50(cfg)[0],
        "inception": lambda cfg: build_inception_v3(cfg)[0],
        "nmt": lambda cfg: build_nmt(cfg)[0],
        "candle_uno": lambda cfg: build_candle_uno(cfg)[0],
    }


def lint_main(argv) -> int:
    """``flexflow-tpu lint --model transformer --strategy s.pb``: run the
    static verifier (flexflow_tpu.analysis) over a builtin model graph +
    a strategy file and print structured FFxxx diagnostics.  Exit codes:
    0 clean (INFO/WARN only), 1 any ERROR diagnostic, 2 usage/load
    failure.  Entirely device-free: a 1024-chip strategy lints on a
    laptop (no mesh is built, nothing is traced)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="flexflow-tpu lint",
        description="statically verify a strategy against a builtin "
                    "model graph (docs/verifier.md), or a whole model "
                    "fleet's co-residency (--fleet, docs/serving.md "
                    "'Model fleets')")
    parser.add_argument("--model",
                        help=f"builtin graph: "
                             f"{', '.join(sorted(_lint_builders()))}")
    parser.add_argument("--fleet", default="",
                        help="fleet registry JSON: run the static "
                             "co-residency gate over every tenant "
                             "(summed FF108 + KV bytes vs the HBM "
                             "budget — FF130 on overflow) instead of "
                             "a single-model lint")
    parser.add_argument("--strategy", default="",
                        help="strategy .pb (reference wire format); "
                             "omit to lint the graph alone")
    parser.add_argument("--devices", type=int, default=0,
                        help="machine size device ids must fit "
                             "(default: inferred mesh product)")
    parser.add_argument("--mesh", default="",
                        help="mesh factorization, e.g. n=4,c=2 "
                             "(default: inferred from the strategy)")
    parser.add_argument("-b", "--batch-size", type=int, default=64)
    parser.add_argument("--hbm-gb", type=float, default=0.0,
                        help="per-chip HBM budget override in GB "
                             "(default: attached/assumed device spec)")
    parser.add_argument("--calibration", default="",
                        help="CalibrationTable JSON (flexflow-tpu "
                             "calibrate): applies its measured "
                             "DeviceSpec overrides and xla_temp_factor "
                             "to the FF108 HBM pass, so lint judges "
                             "the same calibrated budget the search "
                             "does (docs/strategy_search.md)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable report on stdout")
    parser.add_argument("--concurrency", action="store_true",
                        help="run the whole-program lock-discipline "
                             "pass (FF150-FF154, docs/concurrency.md) "
                             "over flexflow_tpu/ instead of a "
                             "model/strategy lint")
    parser.add_argument("--no-resharding", action="store_true",
                        help="skip the FF109 hotspot report")
    parser.add_argument("--serve-slots", type=int, default=0,
                        help="size a token-generation deployment: add "
                             "the KV cache for N concurrent decode "
                             "slots to the FF108/FF121 memory gates "
                             "(docs/serving.md 'Token generation')")
    parser.add_argument("--serve-seq", type=int, default=0,
                        help="generation cache length per slot "
                             "(default: the model's sequence length)")
    parser.add_argument("--serve-kv-page", type=int, default=0,
                        help="KV page size of the deployment being "
                             "sized (default: the engine default) — "
                             "pass the same value the engine runs "
                             "with, or lint charges a different pool")
    parser.add_argument("--serve-kv-pages", type=int, default=0,
                        help="KV pool pages (0 = auto, the dense "
                             "worst case slots x ceil(seq/page))")
    parser.add_argument("--serve-prefill-chunk", type=int, default=0,
                        help="prompt chunk of the deployment (0 = whole "
                             "prompts): sizes the rows of windowed "
                             "attention layers, window + chunk a slot")
    args = parser.parse_args(argv)

    if args.concurrency:
        from .analysis.concurrency import concurrency_main
        return concurrency_main(as_json=args.json)
    if args.fleet:
        return _lint_fleet(args)
    builders = _lint_builders()
    if args.model is None:
        print("lint: --model is required (or --fleet / --concurrency "
              "for the whole-tree gates)", file=sys.stderr)
        return 2
    if args.model not in builders:
        print(f"lint: unknown model {args.model!r} (have "
              f"{', '.join(sorted(builders))})", file=sys.stderr)
        return 2
    from .config import FFConfig
    cfg = FFConfig(batch_size=args.batch_size)
    model = builders[args.model](cfg)

    strategies = None
    if args.strategy:
        from .strategy.proto import load_strategy_file
        try:
            strategies = load_strategy_file(args.strategy)
        except (OSError, ValueError) as e:
            print(f"lint: cannot load {args.strategy}: {e}",
                  file=sys.stderr)
            return 2

    mesh_shape = None
    if args.mesh:
        try:
            mesh_shape = {k: int(v) for k, v in
                          (kv.split("=") for kv in args.mesh.split(","))}
            from .parallel.mesh import AbstractMesh
            AbstractMesh(mesh_shape)  # axis-name/size validation
        except ValueError as e:
            print(f"lint: bad --mesh {args.mesh!r} (want n=4,c=2): {e}",
                  file=sys.stderr)
            return 2

    spec = None
    temp_factor = None
    if args.calibration:
        from .search.calibration import CalibrationTable, calibrated_spec
        try:
            table = CalibrationTable.load(args.calibration)
        except (OSError, ValueError) as e:
            print(f"lint: cannot load {args.calibration}: {e}",
                  file=sys.stderr)
            return 2
        spec = calibrated_spec(table)
        temp_factor = table.xla_temp_factor
    if args.hbm_gb > 0:
        import dataclasses

        from .search.cost_model import spec_for_device
        spec = dataclasses.replace(spec or spec_for_device(),
                                   hbm_capacity=args.hbm_gb * 1e9)

    kv_bytes = 0.0
    if args.serve_kv_page < 0 or args.serve_kv_pages < 0:
        print("lint: --serve-kv-page/--serve-kv-pages must be >= 0 "
              "(0 = default/auto)", file=sys.stderr)
        return 2
    if args.serve_slots > 0:
        # the generation engine's preallocated KV cache — the SAME
        # scalar the runtime reports (analysis.kv_memory), so the FF108
        # gate and the engine cannot disagree about deployment fit
        from .analysis.kv_memory import (default_serve_seq, dtype_bytes,
                                         kv_cache_bytes)
        seq = args.serve_seq or default_serve_seq(model.input_tensors)
        if not seq or seq <= 0:
            print("lint: --serve-slots needs --serve-seq (the model "
                  "has no sequence-shaped input to default from)",
                  file=sys.stderr)
            return 2
        shape_for_kv = mesh_shape
        if shape_for_kv is None:
            from .analysis.strategy_passes import infer_mesh_shape
            shape_for_kv, _ = infer_mesh_shape(
                strategies or {}, model.layers, args.devices or 10 ** 9)
        kv_bytes = kv_cache_bytes(
            model.layers, shape_for_kv, args.serve_slots, seq,
            kv_dtype_bytes=dtype_bytes(cfg.compute_dtype),
            page_size=args.serve_kv_page,
            num_pages=args.serve_kv_pages,
            prefill_chunk=args.serve_prefill_chunk)

    from .analysis import verify
    report = verify(
        model.layers, strategies, mesh_shape=mesh_shape,
        num_devices=args.devices or None,
        input_tensors=model.input_tensors,
        final_tensors=model.layers[-1].outputs if model.layers else (),
        parameters=model.parameters, spec=spec,
        xla_temp_factor=temp_factor,
        check_resharding=not args.no_resharding,
        extra_state_bytes=kv_bytes)
    print(report.render_json() if args.json else report.render_text())
    return 1 if report.errors else 0


def _load_fleet_registry(path: str, what: str):
    """Load + schema-validate a fleet registry JSON for lint/explain
    (returns the registry or prints the problems and returns None)."""
    import json as _json

    from .serving.fleet import ModelRegistry, validate_fleet_json
    try:
        with open(path) as f:
            obj = _json.load(f)
    except (OSError, ValueError) as e:
        print(f"{what}: cannot load {path}: {e}", file=sys.stderr)
        return None
    probs = validate_fleet_json(obj)
    if probs:
        for p in probs:
            print(f"{what}: {path}: {p}", file=sys.stderr)
        return None
    try:
        return ModelRegistry.from_json(obj)
    except ValueError as e:
        print(f"{what}: {path}: {e}", file=sys.stderr)
        return None


def _lint_fleet(args) -> int:
    """``flexflow-tpu lint --fleet fleet.json``: the device-free
    co-residency gate — does the whole fleet FIT on the HBM?  Sums the
    FF108-accounted per-device peak (+ KV caches for generation
    tenants) across every tenant; exit 1 on FF130 (over budget), with
    an FF131 INFO breakdown row per tenant either way."""
    registry = _load_fleet_registry(args.fleet, "lint")
    if registry is None:
        return 2
    spec = None
    temp_factor = None
    if args.calibration:
        from .search.calibration import CalibrationTable, calibrated_spec
        try:
            table = CalibrationTable.load(args.calibration)
        except (OSError, ValueError) as e:
            print(f"lint: cannot load {args.calibration}: {e}",
                  file=sys.stderr)
            return 2
        spec = calibrated_spec(table)
        temp_factor = table.xla_temp_factor
    if args.hbm_gb > 0:
        hbm_gb = args.hbm_gb
    else:
        hbm_gb = registry.hbm_gb
    from .serving.fleet import fleet_gate_report
    report, _rows = fleet_gate_report(
        registry, hbm_gb=hbm_gb, device_spec=spec,
        xla_temp_factor=temp_factor)
    print(report.render_json() if args.json else report.render_text())
    return 1 if report.errors else 0


def explain_main(argv) -> int:
    """``flexflow-tpu explain --model M --strategy s.pb --mesh n=16,c=4``:
    the static what-will-the-runtime-do report (docs/verifier.md
    "explain") — propagated shardings, predicted FF120 replicate
    fallbacks, the per-edge communication plan (reshard/allgather/
    allreduce volumes + ``comm_plan_digest``), and the liveness HBM
    timeline with its peak-owning ops.  Entirely device-free: a
    64-device mesh spec is explained from a CPU-only machine without
    allocating a single jax device.  Exit codes: 0 report produced,
    2 usage/load failure (unlike lint, explain REPORTS — it does not
    gate; run lint for the pass/fail judgement)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="flexflow-tpu explain",
        description="device-free sharding / communication / memory "
                    "report for a strategy (docs/verifier.md)")
    parser.add_argument("--model",
                        help=f"builtin graph: "
                             f"{', '.join(sorted(_lint_builders()))}")
    parser.add_argument("--fleet", default="",
                        help="fleet registry JSON: report every "
                             "tenant's per-device residency breakdown "
                             "(params + KV + FF108 peak) and the fleet "
                             "total instead of a single-model report")
    parser.add_argument("--strategy", default="",
                        help="strategy .pb; omit for the default "
                             "data-parallel plan")
    parser.add_argument("--mesh", default="",
                        help="mesh factorization, e.g. n=16,c=4 "
                             "(default: inferred from the strategy)")
    parser.add_argument("--devices", type=int, default=0,
                        help="machine size (default: mesh product)")
    parser.add_argument("-b", "--batch-size", type=int, default=64)
    parser.add_argument("--hbm-gb", type=float, default=0.0,
                        help="per-chip HBM budget override in GB")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable report on stdout")
    parser.add_argument("--out", default="",
                        help="also write the JSON report here")
    parser.add_argument("--serve-slots", type=int, default=0,
                        help="size a token-generation deployment: "
                             "report the KV cache for N decode slots "
                             "inside the memory timeline")
    parser.add_argument("--serve-seq", type=int, default=0,
                        help="generation cache length per slot "
                             "(default: the model's sequence length)")
    parser.add_argument("--serve-kv-page", type=int, default=0,
                        help="KV page size of the deployment being "
                             "explained (default: the engine default)")
    parser.add_argument("--serve-kv-pages", type=int, default=0,
                        help="KV pool pages (0 = auto, the dense "
                             "worst case)")
    parser.add_argument("--serve-prefill-chunk", type=int, default=0,
                        help="prompt chunk of the deployment (0 = whole "
                             "prompts): sizes windowed layers' rows")
    args = parser.parse_args(argv)

    if args.fleet:
        return _explain_fleet(args)
    builders = _lint_builders()
    if args.model is None:
        print("explain: --model is required (or --fleet for the "
              "residency breakdown)", file=sys.stderr)
        return 2
    if args.model not in builders:
        print(f"explain: unknown model {args.model!r} (have "
              f"{', '.join(sorted(builders))})", file=sys.stderr)
        return 2
    from .config import FFConfig
    cfg = FFConfig(batch_size=args.batch_size)
    model = builders[args.model](cfg)

    strategies = None
    if args.strategy:
        from .strategy.proto import load_strategy_file
        try:
            strategies = load_strategy_file(args.strategy)
        except (OSError, ValueError) as e:
            print(f"explain: cannot load {args.strategy}: {e}",
                  file=sys.stderr)
            return 2

    mesh_shape = None
    if args.mesh:
        try:
            mesh_shape = {k: int(v) for k, v in
                          (kv.split("=") for kv in args.mesh.split(","))}
            from .parallel.mesh import AbstractMesh
            AbstractMesh(mesh_shape)  # axis-name/size validation
        except ValueError as e:
            print(f"explain: bad --mesh {args.mesh!r} (want n=4,c=2): "
                  f"{e}", file=sys.stderr)
            return 2

    spec = None
    if args.hbm_gb > 0:
        import dataclasses

        from .search.cost_model import spec_for_device
        spec = dataclasses.replace(spec_for_device(),
                                   hbm_capacity=args.hbm_gb * 1e9)

    if args.serve_kv_page < 0 or args.serve_kv_pages < 0:
        print("explain: --serve-kv-page/--serve-kv-pages must be >= 0 "
              "(0 = default/auto)", file=sys.stderr)
        return 2
    serve_seq = args.serve_seq
    if args.serve_slots > 0 and serve_seq <= 0:
        from .analysis.kv_memory import default_serve_seq
        serve_seq = default_serve_seq(model.input_tensors) or 0
        if serve_seq <= 0:
            print("explain: --serve-slots needs --serve-seq (the model "
                  "has no sequence-shaped input to default from)",
                  file=sys.stderr)
            return 2

    from .analysis import explain_report, render_explain_text
    rep = explain_report(
        args.model, model.layers, strategies, mesh_shape=mesh_shape,
        num_devices=args.devices or None, spec=spec,
        serve_slots=args.serve_slots, serve_seq=serve_seq,
        serve_kv_page=args.serve_kv_page,
        serve_kv_pages=args.serve_kv_pages,
        serve_prefill_chunk=args.serve_prefill_chunk)
    if args.json:
        import json as _json
        text = _json.dumps(rep, indent=2)
    else:
        text = render_explain_text(rep)
    print(text)
    if args.out:
        import json as _json
        with open(args.out, "w") as f:
            f.write(_json.dumps(rep, indent=2) + "\n")
        print(f"# wrote {args.out}", file=sys.stderr)
    return 0


def _explain_fleet(args) -> int:
    """``flexflow-tpu explain --fleet fleet.json``: per-tenant
    residency breakdown (params / KV / FF108 peak bytes, each tenant's
    mesh) + the fleet total vs the HBM budget — the report half of the
    co-residency gate (run ``lint --fleet`` for the pass/fail
    judgement)."""
    registry = _load_fleet_registry(args.fleet, "explain")
    if registry is None:
        return 2
    from .serving.fleet import fleet_gate_report
    from .serving.fleet.gate import resolve_budget
    hbm_gb = args.hbm_gb or registry.hbm_gb
    report, rows = fleet_gate_report(registry, hbm_gb=hbm_gb)
    # the verdict IS the gate's: FF130 present <=> over budget — the
    # report half must never re-derive (and potentially contradict)
    # what lint --fleet gates on
    budget = resolve_budget(hbm_gb)
    total = sum(r["ff108_bytes"] for r in rows)
    rep = {
        "fleet": args.fleet,
        "hbm_budget_gb": round(budget / 1e9, 3),
        "total_gb": round(total / 1e9, 3),
        "fits": not report.errors,
        "tenants": rows,
    }
    if args.json:
        import json as _json
        text = _json.dumps(rep, indent=2)
    else:
        lines = [f"fleet {args.fleet}: {len(rows)} tenant(s), "
                 f"{rep['total_gb']} GB / {rep['hbm_budget_gb']} GB "
                 f"budget — {'FITS' if rep['fits'] else 'OVER'}"]
        for r in rows:
            kv = (f", kv {r['kv_bytes'] / 1e9:.3f} GB "
                  f"({r['kv_slots']}x{r['kv_seq']})"
                  if r["kv_bytes"] else "")
            lines.append(
                f"  {r['name']} [{r['engine']}] mesh {r['mesh']}: "
                f"peak {r['ff108_bytes'] / 1e9:.3f} GB (params "
                f"{r['params_bytes'] / 1e9:.3f} GB{kv})")
        text = "\n".join(lines)
    print(text)
    if args.out:
        import json as _json
        with open(args.out, "w") as f:
            f.write(_json.dumps(rep, indent=2) + "\n")
        print(f"# wrote {args.out}", file=sys.stderr)
    return 0


def elastic_main(argv) -> int:
    """``flexflow-tpu elastic [flags] -- <script.py> [script args]``:
    run ``--nprocs`` copies of the script under the hardened elastic
    supervisor (flexflow_tpu/parallel/elastic.py) — heartbeat hang
    detection, failure classification, backoff-with-jitter restarts.

    Each worker gets ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES``
    / ``JAX_PROCESS_ID`` in its environment (fresh coordinator port per
    attempt), which ``initialize_distributed()`` — called by any script
    run through this CLI or flexflow_tpu directly — picks up.  Scripts
    resume via ``resilience.elastic_resume(model, workdir)``; the
    supervisor exports ``FF_ELASTIC_WORKDIR`` from ``--workdir``.
    Returns the process exit code (0 on recovered success)."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="flexflow-tpu elastic",
        description="supervise an elastic multi-process training run")
    parser.add_argument("--nprocs", type=int, default=1,
                        help="worker processes per attempt")
    parser.add_argument("--max-restarts", type=int, default=2)
    parser.add_argument("--attempt-timeout", type=float, default=3600.0,
                        metavar="S")
    parser.add_argument("--hang-timeout", type=float, default=None,
                        metavar="S",
                        help="kill an attempt when no rank's heartbeat "
                             "step advances for S seconds (off unless "
                             "set; workers must beat via "
                             "flexflow_tpu.resilience.Heartbeat)")
    parser.add_argument("--workdir", default=".",
                        help="checkpoint directory exported to workers "
                             "as FF_ELASTIC_WORKDIR")
    parser.add_argument("--min-procs", type=int, default=None,
                        help="degrade-and-continue floor: after "
                             "--degrade-after consecutive crash/hang/"
                             "timeout attempts, HALVE the group (not "
                             "below this) and resume on the surviving "
                             "mesh instead of retrying the dead "
                             "topology (docs/elastic.md 'Resharding')")
    parser.add_argument("--degrade-after", type=int, default=2,
                        metavar="N",
                        help="consecutive topology-class failures "
                             "before a degrade step (default 2)")
    parser.add_argument("--backoff-base", type=float, default=0.5,
                        metavar="S")
    parser.add_argument("--backoff-max", type=float, default=30.0,
                        metavar="S")
    parser.add_argument("--backoff-seed", type=int, default=0)
    if "--" not in argv:
        parser.error("separate the worker script with '--': "
                     "flexflow-tpu elastic --nprocs 2 -- train.py -b 64")
    split = argv.index("--")
    args = parser.parse_args(argv[:split])
    worker_cmd = argv[split + 1:]
    if not worker_cmd:
        parser.error("no worker script given after '--'")

    from .parallel.elastic import run_elastic

    # a missing checkpoint dir would fail every attempt's first save
    os.makedirs(args.workdir, exist_ok=True)

    def worker_argv(attempt, port, rank):
        # through the CLI harness, not bare python: FlexFlow flags after
        # the script still parse into the default FFConfig, and main()'s
        # initialize_distributed() picks up the JAX_* env below
        return [sys.executable, "-m", "flexflow_tpu.cli", *worker_cmd]

    def per_rank_env(attempt, port, rank, nprocs):
        # nprocs is the CURRENT world size — the degrade policy may have
        # shrunk it below --nprocs; workers reshard on resume
        return {"JAX_COORDINATOR_ADDRESS": f"localhost:{port}",
                "JAX_NUM_PROCESSES": str(nprocs),
                "JAX_PROCESS_ID": str(rank)}

    report = run_elastic(
        worker_argv, num_processes=args.nprocs,
        max_restarts=args.max_restarts,
        attempt_timeout_s=args.attempt_timeout,
        hang_timeout_s=args.hang_timeout,
        env={"FF_ELASTIC_WORKDIR": os.path.abspath(args.workdir)},
        per_rank_env=per_rank_env,
        backoff_base_s=args.backoff_base, backoff_max_s=args.backoff_max,
        backoff_seed=args.backoff_seed,
        min_processes=args.min_procs, degrade_after=args.degrade_after)
    for i, a in enumerate(report.attempts):
        steps = (" steps=" + ",".join(
            f"r{r}:{s}" for r, s in sorted(a.rank_steps.items()))
            if a.rank_steps else "")
        detail = f" ({a.spawn_error})" if a.spawn_error else ""
        print(f"elastic attempt {i}: cause={a.cause} "
              f"nprocs={a.num_processes} "
              f"rc={a.returncodes} elapsed={a.elapsed_s}s"
              f"{steps}{detail}", file=sys.stderr)
        if a.cause != "ok" and a.failed_rank is not None:
            tail = a.tails.get(a.failed_rank, "").strip()
            if tail:
                print(f"  rank {a.failed_rank} tail: ...{tail[-400:]}",
                      file=sys.stderr)
    if report.success:
        print(f"elastic: success after {report.restarts} restart(s)",
              file=sys.stderr)
        return 0
    print("elastic: FAILED"
          + (" (fail-fast: instant all-rank crash on attempt 0 — "
             "likely an argv/config error)" if report.fail_fast else
             f" after {len(report.attempts)} attempt(s)"),
          file=sys.stderr)
    return 1


if __name__ == "__main__":
    main()
