#!/usr/bin/env bash
# Run the full fault-injection matrix locally with per-case timeouts.
#
# Two halves (docs/elastic.md):
#   fast  — tests/test_faults.py: supervisor-level faults with real OS
#           processes but no jax workers (also run by tier-1 via the
#           `faults` marker)
#   slow  — tests/test_elastic.py: multi-process jax workers, one
#           recovery + loss-parity case per FF_FAULT kind
#
# Usage: scripts/fault_matrix.sh [--fast-only]
# Exit: nonzero if any case fails or times out.

set -u
cd "$(dirname "$0")/.."

FAST_TIMEOUT=${FAST_TIMEOUT:-180}
SLOW_TIMEOUT=${SLOW_TIMEOUT:-900}

declare -a cases=(
  "$FAST_TIMEOUT tests/test_faults.py"
  # grow_at_step / shrink_at_step: in-process live resharding, pinned
  # bit-identical against fixed-mesh references (docs/elastic.md
  # "Resharding"; single-process, 8 virtual CPU devices — tier-1 speed)
  "$FAST_TIMEOUT tests/test_reshard.py"
  # serve_slow_dispatch / serve_fail_dispatch / serve_queue_spike: the
  # serving-side fault kinds driven through the ServingEngine's
  # dispatcher (docs/serving.md "Overload, SLOs & degradation";
  # in-process, injectable clock/sleep — tier-1 speed)
  "$FAST_TIMEOUT tests/test_serving.py::TestServeFaults"
  # serve_cancel_at_token / serve_slow_decode / spec_draft_fail: the
  # token-generation fault kinds driven through the GenerationEngine's
  # decode loop (docs/serving.md "Token generation"; a mid-generation
  # cancel must free its KV slot and fail only its own stream, and an
  # injected draft failure must demote speculation to plain decode
  # without failing ANY stream)
  "$FAST_TIMEOUT tests/test_generation.py::TestGenerationFaults"
  # fleet_load_fail / fleet_swap_at_dispatch: the model-fleet fault
  # kinds — a failed background load must leave serving tenants
  # untouched, and a held publish must land exactly at the pinned
  # dispatch boundary (docs/serving.md "Model fleets")
  "$FAST_TIMEOUT tests/test_fleet.py::TestFleetFaults"
  # migrate_fail_at / route_host_down: the disaggregated-router fault
  # kinds — a failed KV migration handoff must fall back to co-located
  # decode with the exact same tokens (one serve_health event, zero
  # streams fail), a downed host must drain its queued requests to
  # survivors, and the page pools must drain to zero on BOTH engines
  # after every case (docs/serving.md "Disaggregated prefill/decode")
  "$FAST_TIMEOUT tests/test_cluster.py::TestRouterFaults"
  # flight recorder under faults (docs/observability.md): an injected
  # serve_fail_dispatch must leave a dump in FF_FLIGHT_DIR naming the
  # failed dispatch and retaining its request spans; a health edge
  # into `degraded` dumps too, and the flight CLI reads both
  "$FAST_TIMEOUT tests/test_obs.py::TestFlightFaults"
  # tier-1 serving smoke under the lockwatch gate: six threads'
  # round-trips through the ServingEngine whose runtime
  # acquisition-order graph must come out acyclic and a subset of the
  # static FF151 graph (asserted by the conftest session gate, which
  # the FF_LOCKWATCH export below arms for every case here)
  "$FAST_TIMEOUT tests/test_serving.py::test_concurrent_submitters_resolve_correctly"
)
if [ "${1:-}" != "--fast-only" ]; then
  cases+=(
    "$SLOW_TIMEOUT tests/test_elastic.py::test_crash_restart_resume"
    "$SLOW_TIMEOUT tests/test_elastic.py::test_hang_detected_by_heartbeats_and_recovered"
    "$SLOW_TIMEOUT tests/test_elastic.py::test_corrupt_newest_checkpoint_falls_back"
    "$SLOW_TIMEOUT tests/test_elastic.py::test_spawn_fault_consumes_restart_then_recovers"
    "$SLOW_TIMEOUT tests/test_elastic.py::test_exhausted_restarts_reports_failure"
    "$SLOW_TIMEOUT tests/test_elastic.py::test_spawn_failure_consumes_restart"
  )
fi

# each pytest invocation is its own session: keep the in-process
# compilation cache across cases instead of re-clearing it every time
# (tests/conftest.py clears it per session by default)
export FF_TEST_KEEP_CACHE=1

# the dynamic lock-order gate (docs/concurrency.md): every case runs
# with instrumented locks, and tests/conftest.py's session gate then
# asserts the observed acquisition-order graph is acyclic and a
# subset of the static FF151 graph
export FF_LOCKWATCH=1

fails=0
for entry in "${cases[@]}"; do
  t=${entry%% *}
  case=${entry#* }
  echo "=== fault-matrix: $case (timeout ${t}s) ==="
  timeout -k 10 "$t" env JAX_PLATFORMS=cpu \
    python -m pytest "$case" -q -p no:cacheprovider
  rc=$?
  if [ $rc -ne 0 ]; then
    [ $rc -ge 124 ] && echo "TIMEOUT after ${t}s: $case"
    echo "FAIL (rc=$rc): $case"
    fails=$((fails + 1))
  fi
done

echo
if [ $fails -ne 0 ]; then
  echo "fault matrix: $fails case(s) FAILED"
  exit 1
fi
echo "fault matrix: all cases passed"
