#!/usr/bin/env bash
# CI entry point, staged so the verify loop stays usable:
#
#   scripts/ci.sh fast   — fast tier-1 stage only: pytest -m "not slow"
#                          (the sub-10-minute loop).
#                          ZERO failures is the contract: the stage exits
#                          non-zero on ANY failed or errored test.  The
#                          pre-existing-failure allowance (10 known model/
#                          sharding failures tolerated through PR 4) is
#                          gone — those tests are fixed, not skipped, and
#                          any new red is a regression.
#   scripts/ci.sh slow   — the slow-marked suites (hypothesis-heavy property
#                          walls, large-n sweeps, multi-device subprocess
#                          tests); pairs with a separate `fast` job so CI never runs
#                          the fast tier twice
#   scripts/ci.sh faults — fault-matrix smoke only: one resilient oocsort
#                          run per core.faults fault site with retries
#                          enabled, asserting green + byte parity vs the
#                          fault-free run (scripts/fault_matrix.py)
#   scripts/ci.sh dist   — multi-device shard-exchange stage: the
#                          dist-marked subprocess walls at 8 fake devices
#                          (fast rung) and 16 fake devices (slow rung; the
#                          XLA flag is exported so tests/_multidev.py widens
#                          every wall)
#   scripts/ci.sh analyze — static contract analyzer: trace every public
#                          entry point and verify the declared launch
#                          census, sort-free, donation, transfer-byte and
#                          ref-hazard contracts + source lint (compile-only,
#                          no kernel executes; writes ANALYSIS_report.json)
#   scripts/ci.sh [full] — all stages back to back (the one-stop local
#                          verify entry point; dist and analyze run as their
#                          own CI jobs — analyze is repeated in full because
#                          it is seconds-cheap)
#
# The chip benchmark is bench/ (python3 -m bench.run, see PERF.md); it
# needs a TPU and is not a CI stage.
#
# Everything runs on a plain CPU host: the Pallas kernels execute in
# interpret mode (the drivers default to it off-TPU), so the fused-engine
# parity and launch-count gates are exercised on every push without TPU
# hardware.  Extra args after the stage name pass through to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

STAGE="${1:-full}"
if [[ "$STAGE" == "fast" || "$STAGE" == "slow" || "$STAGE" == "faults" \
      || "$STAGE" == "dist" || "$STAGE" == "analyze" \
      || "$STAGE" == "full" ]]; then
  if [[ $# -gt 0 ]]; then shift; fi
else
  STAGE="full"
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# a stage whose marker filter plus user-supplied pass-through args (-k ...)
# collects zero tests exits 5; that is fine for a tier, not a failure of the
# script.  Without pass-through args an empty tier means the marker setup is
# broken and must stay fatal.
PASSTHROUGH=$#
run_stage() {
  local rc=0
  python -m pytest -q "$@" || rc=$?
  if [[ $rc -eq 5 && $PASSTHROUGH -gt 0 ]]; then
    echo "WARNING: this stage collected 0 tests (tolerated: pass-through" \
         "args may filter out an entire tier)"
    rc=0
  fi
  if [[ $rc -ne 0 ]]; then
    exit "$rc"
  fi
}

if [[ "$STAGE" == "analyze" ]]; then
  echo "=== static contract analyzer (compile-only) ==="
  python -m repro.analysis --json ANALYSIS_report.json
  exit 0
fi

if [[ "$STAGE" == "faults" ]]; then
  echo "=== fault-matrix smoke (one resilient run per fault site) ==="
  python scripts/fault_matrix.py
  exit 0
fi

if [[ "$STAGE" == "dist" ]]; then
  # the exported flag only reaches the multi-device subprocesses
  # (tests/_multidev.py reads it for the default width); in-process tests in
  # the dist marker set would see fake devices, so the marker is reserved
  # for subprocess walls (tests/conftest.py invariant)
  echo "=== dist stage: multi-device walls at 8 devices (fast rung) ==="
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    run_stage -m "dist and not slow" "$@"
  echo "=== dist stage: multi-device walls at 16 devices (slow rung) ==="
  XLA_FLAGS="--xla_force_host_platform_device_count=16" \
    run_stage -m "dist and slow" "$@"
  exit 0
fi

if [[ "$STAGE" != "slow" ]]; then
  echo "=== tier-1 tests (fast stage: -m 'not slow') ==="
  run_stage -m "not slow" "$@"
fi

if [[ "$STAGE" == "fast" ]]; then
  exit 0
fi

# faults and analyze run as their own CI jobs; in the local one-stop `full`
# entry point they slot between the tiers
if [[ "$STAGE" == "full" ]]; then
  echo "=== static contract analyzer (compile-only) ==="
  python -m repro.analysis --json ANALYSIS_report.json
  echo "=== fault-matrix smoke (one resilient run per fault site) ==="
  python scripts/fault_matrix.py
fi

echo "=== tier-1 tests (slow stage: -m slow) ==="
run_stage -m "slow" "$@"
