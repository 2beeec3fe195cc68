#!/usr/bin/env python
"""Repo static gate for fleet registries (scripts/static_checks.sh):
every shipped fleet registry JSON (``examples/serving/fleet.json`` and
any ``examples/**/fleet*.json``) must pass
``serving.fleet.validate_fleet_json`` — the SAME schema
``ModelRegistry.from_json`` and ``flexflow-tpu lint --fleet`` enforce,
so a committed registry can never rot silently.

Device-free and jax-free: pure JSON + schema functions.
"""

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    from flexflow_tpu.serving.fleet import validate_fleet_json

    failures = 0

    registries = sorted(
        glob.glob(os.path.join(REPO, "examples", "**", "fleet*.json"),
                  recursive=True))
    for path in registries:
        rel = os.path.relpath(path, REPO)
        try:
            with open(path) as f:
                obj = json.load(f)
        except ValueError as e:
            print(f"FAIL {rel}: not valid JSON: {e}")
            failures += 1
            continue
        probs = validate_fleet_json(obj)
        for p in probs:
            print(f"FAIL {rel}: {p}")
        failures += len(probs)
        if not probs:
            print(f"ok   {rel}: {len(obj['fleet'])} tenant(s)")

    if not registries:
        print("no fleet registries found (nothing to check)")
    if failures:
        print(f"fleet registries: {failures} problem(s)", file=sys.stderr)
        return 1
    print("fleet registries: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
