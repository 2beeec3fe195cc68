#!/usr/bin/env python
"""Schema-gate the ISSUE 15 artifacts (run by scripts/static_checks.sh).

* ``artifacts/gen_prefix_bench_r16.json`` — the paged-KV /
  shared-prefix / chunked-prefill evidence: structural schema PLUS the
  acceptance booleans (prefix-cache TTFT win with bit-identical
  tokens, chunked-prefill stall win at comparable throughput, HBM
  high-water <= the dense baseline, reconciliation) must all be True —
  a regression that flips one can never land silently with the old
  artifact still claiming the win.
* ``artifacts/spec_bench_r17.json`` — the ISSUE 16 speculative-
  decoding evidence: the gamma x sampling sweep's structural schema
  PLUS the acceptance booleans (tokens/s win over the gamma=0 arm,
  greedy bit-parity, sampled reproducibility) must all be True, and
  the win boolean must agree with the recorded per-arm tokens_per_s.
* ``artifacts/disagg_bench_r19.json`` — the ISSUE 19 disaggregated
  prefill/decode evidence: colo chunked arms + the disagg arm's
  structural schema PLUS the acceptance booleans (victim stall and
  TPOT p95 strictly better than the goodput-qualified colo baseline,
  goodput no worse, colo/disagg tokens bit-identical with the prefix
  cache on AND off, cross-engine reconciliation, every stream
  migrated) must all be True, and the stall/goodput booleans must
  agree with the recorded per-arm rows.
* ``artifacts/pallas_flags_*.json`` — the per-device-kind Pallas
  decision artifacts (one per device kind a microbench decided): each
  must carry the schema version, device kind, and an on/speedup/row
  triple per flag.  Zero committed decisions is fine (no chip window
  yet); a MALFORMED one is not.

No third-party deps — must run on a bare CPython.
"""

from __future__ import annotations

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX_BENCH = os.path.join(REPO, "artifacts", "gen_prefix_bench_r16.json")

_ACCEPTANCE_KEYS = ("ttft_cache_win", "prefix_parity",
                    "chunked_stall_win", "throughput_comparable",
                    "hbm_high_water_ok", "reconciliation_ok")
_PALLAS_FLAGS = ("pallas_norm",)


def _fail(msg: str) -> int:
    print(f"check_gen_artifacts: {msg}")
    return 1


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def check_prefix_bench(path: str = PREFIX_BENCH) -> int:
    try:
        with open(path) as f:
            p = json.load(f)
    except OSError as e:
        return _fail(f"cannot read {os.path.relpath(path, REPO)}: {e}")
    except ValueError as e:
        return _fail(f"{os.path.relpath(path, REPO)} is not JSON: {e}")
    rc = 0
    if p.get("bench") != "gen-prefix":
        rc |= _fail(f"bench must be 'gen-prefix', got {p.get('bench')!r}")
    for key in ("config", "prefix_cache", "chunked_prefill",
                "kv_memory", "acceptance"):
        if not isinstance(p.get(key), dict):
            rc |= _fail(f"missing/non-object section {key!r}")
    if rc:
        return rc
    for arm in ("on", "off"):
        row = p["prefix_cache"].get(arm)
        if not isinstance(row, dict):
            rc |= _fail(f"prefix_cache.{arm} missing")
            continue
        for k in ("tokens_per_s", "prefix_hit_rate",
                  "kv_high_water_bytes"):
            if not _num(row.get(k)):
                rc |= _fail(f"prefix_cache.{arm}.{k} must be numeric")
        if not isinstance(row.get("ttft"), dict) \
                or not _num(row["ttft"].get("p95_ms")):
            rc |= _fail(f"prefix_cache.{arm}.ttft.p95_ms missing")
        if row.get("reconciled") is not True:
            rc |= _fail(f"prefix_cache.{arm}.reconciled must be true")
        if "device_kind" not in row or "comm_plan_digest" not in row:
            rc |= _fail(f"prefix_cache.{arm} lacks the PR 7/PR 9 "
                        f"device_kind/comm_plan_digest stamps")
    for arm in ("monolithic", "chunked"):
        row = p["chunked_prefill"].get(arm)
        if not isinstance(row, dict) \
                or not _num(row.get("victim_max_gap_ms")) \
                or not _num(row.get("tokens_per_s")):
            rc |= _fail(f"chunked_prefill.{arm} needs numeric "
                        f"victim_max_gap_ms/tokens_per_s")
    for k in ("dense_baseline_bytes", "page_bytes",
              "high_water_bytes_cache_on"):
        if not _num(p["kv_memory"].get(k)):
            rc |= _fail(f"kv_memory.{k} must be numeric")
    acc = p["acceptance"]
    for k in _ACCEPTANCE_KEYS:
        if acc.get(k) is not True:
            rc |= _fail(f"acceptance.{k} must be true (got {acc.get(k)!r})"
                        f" — the committed evidence no longer shows the "
                        f"win; re-run serve-bench --generate --prefix")
    # cross-checks: booleans must agree with the rows they summarize
    on, off = p["prefix_cache"]["on"], p["prefix_cache"]["off"]
    if not (on["ttft"]["p95_ms"] < off["ttft"]["p95_ms"]):
        rc |= _fail("ttft_cache_win contradicts the recorded p95s")
    mono = p["chunked_prefill"]["monolithic"]
    chk = p["chunked_prefill"]["chunked"]
    if not (chk["victim_max_gap_ms"] < mono["victim_max_gap_ms"]):
        rc |= _fail("chunked_stall_win contradicts the recorded gaps")
    # strict < the dense baseline AND <= the no-cache arm: high_water
    # <= pool size holds trivially, so only the strict form gates
    if not (on["kv_high_water_bytes"]
            < p["kv_memory"]["dense_baseline_bytes"]
            and on["kv_high_water_bytes"]
            <= off["kv_high_water_bytes"]):
        rc |= _fail("hbm_high_water_ok contradicts the recorded bytes")
    if rc == 0:
        print(f"check_gen_artifacts: "
              f"{os.path.relpath(path, REPO)} OK "
              f"(ttft p95 {on['ttft']['p95_ms']} < "
              f"{off['ttft']['p95_ms']} ms, stall "
              f"{chk['victim_max_gap_ms']} < "
              f"{mono['victim_max_gap_ms']} ms, hit rate "
              f"{on['prefix_hit_rate']})")
    return rc


SPEC_BENCH = os.path.join(REPO, "artifacts", "spec_bench_r17.json")

_SPEC_ACCEPTANCE = ("spec_tokens_win", "greedy_parity",
                    "sampled_reproducible")


def check_spec_bench(path: str = SPEC_BENCH) -> int:
    try:
        with open(path) as f:
            p = json.load(f)
    except OSError as e:
        return _fail(f"cannot read {os.path.relpath(path, REPO)}: {e}")
    except ValueError as e:
        return _fail(f"{os.path.relpath(path, REPO)} is not JSON: {e}")
    rc = 0
    if p.get("bench") != "gen-spec":
        rc |= _fail(f"bench must be 'gen-spec', got {p.get('bench')!r}")
    for key in ("config", "arms", "acceptance"):
        if not isinstance(p.get(key), dict):
            rc |= _fail(f"missing/non-object section {key!r}")
    if rc:
        return rc
    if "device_kind" not in p or "comm_plan_digest" not in p:
        rc |= _fail("payload lacks the PR 7/PR 9 device_kind/"
                    "comm_plan_digest stamps")
    for mode in ("greedy", "temperature"):
        rows = p["arms"].get(mode)
        if not isinstance(rows, list) or len(rows) < 2:
            rc |= _fail(f"arms.{mode} must list the gamma sweep "
                        f"(>= 2 rows: gamma=0 baseline + speculation)")
            continue
        for row in rows:
            for k in ("tokens_per_s", "tpot_p50_ms", "tpot_p95_ms",
                      "tpot_p99_ms", "accept_rate",
                      "draft_dispatches"):
                if not _num(row.get(k)):
                    rc |= _fail(f"arms.{mode}[{row.get('arm')!r}].{k} "
                                f"must be numeric")
            if not isinstance(row.get("arm"), str):
                rc |= _fail(f"arms.{mode} row lacks an 'arm' label")
        if rows[0].get("arm") != "g0":
            rc |= _fail(f"arms.{mode}[0] must be the gamma=0 baseline")
    if rc:
        return rc
    acc = p["acceptance"]
    for k in _SPEC_ACCEPTANCE:
        if acc.get(k) is not True:
            rc |= _fail(f"acceptance.{k} must be true (got {acc.get(k)!r})"
                        f" — the committed evidence no longer shows the "
                        f"win; re-run serve-bench --generate --speculate")
    # cross-check: the win boolean must agree with the recorded rows —
    # the BEST greedy speculation arm strictly beats the gamma=0 arm
    greedy = p["arms"]["greedy"]
    base = greedy[0]["tokens_per_s"]
    best = max(r["tokens_per_s"] for r in greedy[1:])
    if not best > base:
        rc |= _fail("spec_tokens_win contradicts the recorded "
                    f"tokens_per_s (best spec {best} vs g0 {base})")
    if rc == 0:
        print(f"check_gen_artifacts: "
              f"{os.path.relpath(path, REPO)} OK "
              f"(greedy {base} -> {best} tok/s, accept "
              f"{greedy[1].get('accept_rate')})")
    return rc


DISAGG_BENCH = os.path.join(REPO, "artifacts", "disagg_bench_r19.json")

_DISAGG_ACCEPTANCE = ("tpot_p95_better", "victim_stall_better",
                      "goodput_no_worse", "tokens_bit_identical",
                      "reconciliation_ok", "all_migrated")


def check_disagg_bench(path: str = DISAGG_BENCH) -> int:
    try:
        with open(path) as f:
            p = json.load(f)
    except OSError as e:
        return _fail(f"cannot read {os.path.relpath(path, REPO)}: {e}")
    except ValueError as e:
        return _fail(f"{os.path.relpath(path, REPO)} is not JSON: {e}")
    rc = 0
    if p.get("bench") != "disagg":
        rc |= _fail(f"bench must be 'disagg', got {p.get('bench')!r}")
    for key in ("config", "colo", "disagg", "parity", "acceptance"):
        if not isinstance(p.get(key), dict):
            rc |= _fail(f"missing/non-object section {key!r}")
    if rc:
        return rc
    if "device_kind" not in p or "comm_plan_digest" not in p:
        rc |= _fail("payload lacks the PR 7/PR 9 device_kind/"
                    "comm_plan_digest stamps")
    rows = dict(p["colo"])
    rows["disagg"] = p["disagg"]
    for name, row in rows.items():
        if not isinstance(row, dict):
            rc |= _fail(f"arm {name!r} must be an object")
            continue
        for k in ("victim_max_gap_ms", "goodput_toks_per_s"):
            if not _num(row.get(k)):
                rc |= _fail(f"{name}.{k} must be numeric")
        if not isinstance(row.get("victim_tpot"), dict) \
                or not _num(row["victim_tpot"].get("p95_ms")):
            rc |= _fail(f"{name}.victim_tpot.p95_ms missing")
        if row.get("reconciliation_ok") is not True:
            rc |= _fail(f"{name}.reconciliation_ok must be true")
    for k in ("migrations", "migrated_bytes", "routes"):
        if not _num(p["disagg"].get(k)):
            rc |= _fail(f"disagg.{k} must be numeric")
    if rc:
        return rc
    acc = p["acceptance"]
    for k in _DISAGG_ACCEPTANCE:
        if acc.get(k) is not True:
            rc |= _fail(f"acceptance.{k} must be true (got {acc.get(k)!r})"
                        f" — the committed evidence no longer shows the "
                        f"win; re-run serve-bench --disagg")
    base = rows.get(acc.get("baseline_arm") or "")
    if not isinstance(base, dict):
        rc |= _fail(f"acceptance.baseline_arm {acc.get('baseline_arm')!r}"
                    f" names no recorded colo arm")
        return rc
    # cross-checks: booleans must agree with the rows they summarize
    dis = p["disagg"]
    if not (dis["victim_max_gap_ms"] < base["victim_max_gap_ms"]
            and dis["victim_tpot"]["p95_ms"]
            < base["victim_tpot"]["p95_ms"]):
        rc |= _fail("victim_stall_better/tpot_p95_better contradict "
                    "the recorded baseline-arm rows")
    chunked = [v for k, v in p["colo"].items() if k != "chunk0"]
    if chunked and not all(dis["goodput_toks_per_s"]
                           >= r["goodput_toks_per_s"] for r in chunked):
        rc |= _fail("goodput_no_worse contradicts the recorded "
                    "chunked-arm goodputs")
    if not (p["parity"].get("prefix_on") is True
            and p["parity"].get("prefix_off") is True):
        rc |= _fail("tokens_bit_identical contradicts the parity rows")
    if rc == 0:
        print(f"check_gen_artifacts: "
              f"{os.path.relpath(path, REPO)} OK "
              f"(stall {dis['victim_max_gap_ms']} < "
              f"{base['victim_max_gap_ms']} ms vs "
              f"{acc['baseline_arm']}, goodput "
              f"{dis['goodput_toks_per_s']} tok/s, "
              f"{dis['migrations']} migrations)")
    return rc


def check_pallas_decisions() -> int:
    rc = 0
    paths = sorted(glob.glob(os.path.join(REPO, "artifacts",
                                          "pallas_flags_*.json")))
    for path in paths:
        rel = os.path.relpath(path, REPO)
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError) as e:
            rc |= _fail(f"{rel}: unreadable/not JSON: {e}")
            continue
        if d.get("schema_version") != 1 \
                or d.get("artifact") != "pallas-flags-decision":
            rc |= _fail(f"{rel}: wrong schema_version/artifact tag")
            continue
        if not isinstance(d.get("device_kind"), str) \
                or not d["device_kind"]:
            rc |= _fail(f"{rel}: device_kind must be a nonempty string")
        flags = d.get("flags")
        if not isinstance(flags, dict) or not flags:
            rc |= _fail(f"{rel}: flags must be a nonempty object")
            continue
        for flag, ent in flags.items():
            if flag not in _PALLAS_FLAGS:
                rc |= _fail(f"{rel}: unknown flag {flag!r} "
                            f"(have {_PALLAS_FLAGS})")
                continue
            if not isinstance(ent, dict) \
                    or not isinstance(ent.get("on"), bool) \
                    or not (ent.get("speedup") is None
                            or _num(ent["speedup"])) \
                    or not isinstance(ent.get("row"), dict):
                rc |= _fail(f"{rel}: flags.{flag} needs "
                            f"{{on: bool, speedup: number|null, "
                            f"row: object}}")
    if rc == 0:
        print(f"check_gen_artifacts: {len(paths)} pallas decision "
              f"artifact(s) OK")
    return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--pallas-only" in argv:
        return check_pallas_decisions()
    rc = check_prefix_bench()
    rc |= check_spec_bench()
    rc |= check_disagg_bench()
    rc |= check_pallas_decisions()
    return rc


if __name__ == "__main__":
    sys.exit(main())
