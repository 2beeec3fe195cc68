#!/usr/bin/env bash
# Repo static-check gate: ruff (config in pyproject.toml [tool.ruff]) +
# the custom AST lint (scripts/repo_lint.py) enforcing repo invariants
# (atomic checkpoint writes, diagnostics-not-warnings in strategy paths,
# seeded RNG in tests).  Run from anywhere; nonzero exit on any finding.
#
#   scripts/static_checks.sh            # lint flexflow_tpu/ tests/ scripts/
#   scripts/static_checks.sh path.py    # lint specific paths
#
# ruff is optional at runtime (some containers don't ship it); when
# absent the gate still runs a bytecode-compile pass over the library so
# syntax errors never reach CI, plus the full repo lint.  Install ruff
# to get the complete gate — the pinned config makes it reproducible.
set -u
cd "$(dirname "$0")/.."

rc=0

if command -v ruff >/dev/null 2>&1 || python -c 'import ruff' 2>/dev/null; then
    echo "== ruff check =="
    if command -v ruff >/dev/null 2>&1; then
        ruff check "${@:-flexflow_tpu tests scripts}" || rc=1
    else
        python -m ruff check "${@:-flexflow_tpu tests scripts}" || rc=1
    fi
else
    echo "== ruff not installed: falling back to compileall =="
    python -m compileall -q flexflow_tpu scripts || rc=1
fi

echo "== repo lint (scripts/repo_lint.py) =="
python scripts/repo_lint.py "$@" || rc=1

# the fflock concurrency pass (docs/concurrency.md): whole-program
# lockset inference + deadlock-order analysis over flexflow_tpu/ —
# FF150/FF151/FF154 are ERRORs and fail the gate
echo "== concurrency lint (lint --concurrency) =="
python -m flexflow_tpu.cli lint --concurrency || rc=1

# the committed calibration seed table must parse against its schema
# and carry a digest matching its content (flexflow-tpu calibrate --check)
echo "== calibration seed schema (calibrate --check) =="
python -m flexflow_tpu.cli calibrate --check \
    flexflow_tpu/search/calibration_seed.json || rc=1

# shipped example strategies must keep linting clean and producing
# schema-valid `lint --json` / `explain --json` reports — a committed
# .pb (or a report-schema change) can never rot silently
echo "== shipped strategy artifacts (lint + explain) =="
python scripts/check_strategy_artifacts.py || rc=1

# fleet registry JSONs (examples/**/fleet*.json) must pass the ONE
# schema lint/ModelRegistry enforce — docs/serving.md "Model fleets"
echo "== fleet registries (schema) =="
python scripts/check_fleet_artifacts.py || rc=1

if [ "$rc" -eq 0 ]; then
    echo "static checks: OK"
else
    echo "static checks: FAILED" >&2
fi
exit $rc
