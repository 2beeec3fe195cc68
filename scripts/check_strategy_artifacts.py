#!/usr/bin/env python
"""Gate the shipped example strategies (ISSUE 9 CI satellite): every
committed ``artifacts/searched_*.pb`` must still (a) parse, (b) pass
``flexflow-tpu lint`` with no ERROR diagnostics, and (c) produce a
schema-valid ``lint --json`` AND ``explain --json`` report — so a
committed strategy (or a lint/explain schema change) can never rot
silently.  Run by ``scripts/static_checks.sh`` alongside the calibration
seed check; one process, in-process CLI calls (each subprocess
would pay the jax import again).

Exit 0 when every artifact passes, 1 with findings on stdout.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# shipped strategy -> (builtin lint model, batch size it was searched at
# — encoded in the file name)
CASES = [
    ("artifacts/searched_transformer_b8_8dev.pb", "transformer", 8),
    ("artifacts/searched_transformer_b32_8dev.pb", "transformer", 32),
    ("artifacts/searched_inception_v3_b128_8dev.pb", "inception", 128),
    ("artifacts/searched_inception_v3_b128_32dev.pb", "inception", 128),
    ("artifacts/searched_nmt_b256_8dev.pb", "nmt", 256),
]


def _run_json(main, argv):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    try:
        payload = json.loads(buf.getvalue())
    except ValueError as e:
        return rc, None, [f"stdout is not JSON: {e}"]
    return rc, payload, []


def _discover_extra_cases(problems):
    """Any committed ``artifacts/searched_*.pb`` beyond CASES gets
    linted too (ISSUE 20): a new searched strategy must either match
    the ``searched_<model>_b<batch>_<n>dev[...].pb`` naming (model
    inferable -> full lint ride-along) or be added to CASES
    explicitly — never silently skipped."""
    import glob
    import re

    known = {rel for rel, _, _ in CASES}
    lint_model = {"transformer": "transformer", "inception_v3": "inception",
                  "nmt": "nmt"}
    extras = []
    for path in sorted(glob.glob(os.path.join(REPO, "artifacts",
                                              "searched_*.pb"))):
        rel = os.path.relpath(path, REPO)
        if rel in known:
            continue
        m = re.match(r"searched_(?P<model>.+?)_b(?P<batch>\d+)_"
                     r"(?P<ndev>\d+)dev.*\.pb$", os.path.basename(path))
        if m and m.group("model") in lint_model:
            extras.append((rel, lint_model[m.group("model")],
                           int(m.group("batch"))))
        else:
            problems.append(
                f"{rel}: committed searched strategy not covered by the "
                f"artifact gate — rename to searched_<model>_b<batch>_"
                f"<n>dev.pb or add it to CASES")
    return extras


def main() -> int:
    from flexflow_tpu.analysis import (validate_explain_json,
                                       validate_report_json)
    from flexflow_tpu.cli import explain_main, lint_main

    problems = []
    cases = CASES + _discover_extra_cases(problems)
    for rel, model, batch in cases:
        path = os.path.join(REPO, rel)
        if not os.path.exists(path):
            problems.append(f"{rel}: missing (listed in "
                            f"scripts/check_strategy_artifacts.py)")
            continue
        rc, rep, probs = _run_json(
            lint_main, ["--model", model, "--strategy", path,
                        "-b", str(batch), "--json", "--no-resharding"])
        for p in probs:
            problems.append(f"{rel}: lint --json: {p}")
        if rc != 0:
            problems.append(f"{rel}: lint exit {rc} (ERROR diagnostics "
                            f"or load failure) — the shipped strategy "
                            f"no longer verifies against the "
                            f"{model!r} graph")
        if rep is not None:
            for p in validate_report_json(rep):
                problems.append(f"{rel}: lint schema: {p}")
        rc, rep, probs = _run_json(
            explain_main, ["--model", model, "--strategy", path,
                           "-b", str(batch), "--json"])
        for p in probs:
            problems.append(f"{rel}: explain --json: {p}")
        if rc != 0:
            problems.append(f"{rel}: explain exit {rc}")
        if rep is not None:
            for p in validate_explain_json(rep):
                problems.append(f"{rel}: explain schema: {p}")
        # precision-axis backward compatibility (ISSUE 14): every
        # shipped .pb predates the Op.precision field — it must parse
        # with precision == "" on every op AND re-serialize to the
        # EXACT bytes on disk (the writer emits field 6 only when
        # non-default, so pre-extension files round-trip unchanged and
        # their strategy_digest is stable across the extension)
        from flexflow_tpu.strategy.proto import dumps, load_strategy_file
        with open(path, "rb") as f:
            raw = f.read()
        strategies = load_strategy_file(path)
        bad_prec = [n for n, pc in strategies.items() if pc.precision]
        if bad_prec:
            problems.append(
                f"{rel}: shipped strategy carries precision overrides "
                f"{bad_prec[:4]} — pre-extension artifacts must read "
                f"as default precision")
        if dumps(strategies) != raw:
            problems.append(
                f"{rel}: loads->dumps is not byte-identical — the "
                f"precision proto extension changed the wire encoding "
                f"of a pre-extension file")
    for p in problems:
        print(p)
    if problems:
        print(f"check_strategy_artifacts: {len(problems)} finding(s)",
              file=sys.stderr)
        return 1
    print(f"check_strategy_artifacts: {len(cases)} shipped strategies "
          f"lint + explain clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
