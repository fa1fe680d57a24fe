#!/usr/bin/env bash
# Pre-commit check: graftlint (the repo's JAX/SPMD-aware static analyzer)
# plus a bytecode-compile sweep.  Fast (no tests, no jax programs; a warm
# whole-project cache makes the re-run near-free) — run it before every
# commit; tier-1 runs the same gate via tests/test_graftlint.py.
#
# Default run is the RATCHET: compares against the committed baseline
# (tools/graftlint_baseline.json) and fails on NEW findings, on STALE
# baseline entries, and on unused suppressions — exit 1.  Exit 2 means
# the analyzer itself failed (bad args / crash), which must never be
# confused with a clean run.
#
# --sanitize additionally runs graftsan, the RUNTIME half (compile /
# transfer / dispatch sanitizer smoke suite, dask_ml_tpu/sanitize/),
# ratcheted against tools/sanitize_baseline.json with the same new/stale
# semantics.  Slower (~1 min: it executes real fits on the virtual
# mesh), so it is opt-in here while tier-1 runs it via
# tests/test_sanitize.py.
#
# --drills runs the chaos drill suite (resilience/drills.py): every
# registered FaultPlan injection point against streamed fits at prefetch
# depth 0 and 2, ratcheted against tools/drill_baseline.json (recovery,
# model-equality-vs-unfaulted-twin, and retry-ceiling invariants).
# Tier-1 runs the same gate via tests/test_drills.py.
#
# --locks runs graftlock's RUNTIME half (sanitize/locks.py): the whole
# graftsan smoke suite plus triple_plane (serve + search + ingest in one
# process) under instrumented package locks, ratcheting the observed
# lock-order edge set and thread-roster contracts against
# tools/lock_baseline.json (a NEW edge is a new way to deadlock —
# fail; an unobserved snapshot edge is a warm jit cache — pass).  The
# STATIC half (lock-order-cycle / unguarded-shared-state /
# lock-held-across-dispatch) rides the default graftlint ratchet above,
# and the default path always runs the cheap seeded-fault self-test so
# a blind detector can never gate anything.  Seed a fault through the
# gate itself with DASK_ML_TPU_LOCK_INJECT=inversion|cross-write (the
# gate must exit 1).  Tier-1 runs the same gates via
# tests/test_graftlock.py.
#
# --contracts runs the graftcontract ratchet standalone (design.md
# §23): the five producer/consumer drift rules
# (contract-orphan-producer / contract-dead-consumer /
# contract-roster-drift / contract-baseline-drift /
# contract-undocumented-metric) against tools/contract_baseline.json.
# The SAME rules also ride the default graftlint ratchet above (they
# are registered rules), so this flag is the focused view; and the
# default path always runs the seeded-drift self-test both ways
# (DASK_ML_TPU_CONTRACT_INJECT=orphan-reason|dead-policy must exit 1 —
# a drift detector that cannot fail can never gate).  Tier-1 runs the
# same gates via tests/test_graftcontract.py.
#
# Usage:
#   tools/lint.sh                 # static ratchet gate (text output)
#   tools/lint.sh --json          # same, JSON output (CI trending)
#   tools/lint.sh --sanitize      # static gate + runtime sanitizer gate
#   tools/lint.sh --drills        # static gate + chaos drill gate
#   tools/lint.sh --locks         # static gate + runtime lockset gate
#   tools/lint.sh --contracts     # static gate + contract drift gate
#   tools/lint.sh --rebaseline    # refresh ALL FIVE committed baselines
#                                 # (lint, sanitize, drills, locks,
#                                 # contracts) after intentional
#                                 # changes — each write self-gates its
#                                 # hard invariants; a half-updated set
#                                 # cannot be committed green
#   tools/lint.sh [extra graftlint args]   # passed through
set -euo pipefail
cd "$(dirname "$0")/.."

BASELINE=tools/graftlint_baseline.json
SAN_BASELINE=tools/sanitize_baseline.json
DRILL_BASELINE=tools/drill_baseline.json
LOCK_BASELINE=tools/lock_baseline.json
CONTRACT_BASELINE=tools/contract_baseline.json
CONTRACT_RULES=contract-orphan-producer,contract-dead-consumer
CONTRACT_RULES+=,contract-roster-drift,contract-baseline-drift
CONTRACT_RULES+=,contract-undocumented-metric
MODE=gate
SANITIZE=0
DRILLS=0
LOCKS=0
CONTRACTS=0
EXTRA=()
for a in "$@"; do
  case "$a" in
    --json) EXTRA+=(--format json) ;;
    --rebaseline) MODE=rebaseline ;;
    --sanitize) SANITIZE=1 ;;
    --drills) DRILLS=1 ;;
    --locks) LOCKS=1 ;;
    --contracts) CONTRACTS=1 ;;
    *) EXTRA+=("$a") ;;
  esac
done

if [[ "$MODE" == rebaseline ]]; then
  echo "== graftlint (rebaseline) =="
  JAX_PLATFORMS=cpu python -m dask_ml_tpu.analysis dask_ml_tpu \
    --write-baseline "$BASELINE"
  echo "== graftcontract (rebaseline: contract drift snapshot) =="
  JAX_PLATFORMS=cpu python -m dask_ml_tpu.analysis dask_ml_tpu \
    --select "$CONTRACT_RULES" --write-baseline "$CONTRACT_BASELINE"
  echo "== graftsan (rebaseline: full smoke suite, cold counts) =="
  # all three snapshots refresh in one invocation or the script fails
  # before the gate below — a half-updated set cannot be committed
  # green.  Same 8-virtual-device mesh as the tier-1 harness: ceilings
  # must be calibrated on the topology the gate measures against.
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m dask_ml_tpu.sanitize --write-baseline "$SAN_BASELINE"
  echo "== graftdrill (rebaseline: full chaos drill suite) =="
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m dask_ml_tpu.resilience.drills --write-baseline "$DRILL_BASELINE"
  echo "== graftlock (rebaseline: lock smoke suite, cold edge union) =="
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m dask_ml_tpu.sanitize.locks --write-baseline "$LOCK_BASELINE"
fi

echo "== graftlint (ratchet vs $BASELINE) =="
JAX_PLATFORMS=cpu python -m dask_ml_tpu.analysis dask_ml_tpu \
  --baseline "$BASELINE" ${EXTRA[@]+"${EXTRA[@]}"}

echo "== graftcontract (drift self-test: seeded drift must be caught) =="
# always on the default path: the contract rules just ran green inside
# the full ratchet above, so now each seeded drift
# (DASK_ML_TPU_CONTRACT_INJECT) re-runs them and MUST exit 1 — a drift
# detector that cannot fail can never gate.  No jax programs; the cache
# digests the inject knob, so each arm is warm after its first run and
# the analysis itself is milliseconds.
for inj in orphan-reason dead-policy; do
  rc=0
  JAX_PLATFORMS=cpu DASK_ML_TPU_CONTRACT_INJECT="$inj" \
    python -m dask_ml_tpu.analysis dask_ml_tpu \
    --select "$CONTRACT_RULES" --baseline "$CONTRACT_BASELINE" \
    >/dev/null 2>&1 || rc=$?
  if [[ "$rc" != 1 ]]; then
    echo "graftcontract: seeded-drift self-test FAILED ($inj: exit $rc," \
         "want 1: the contract drift detector is blind)" >&2
    exit 1
  fi
done
echo "graftcontract: 2/2 seeded drifts detected"

echo "== graftlock (detector self-test: seeded faults must be caught) =="
# always on the default path: both seeded faults (an A->B/B->A order
# inversion and a rogue-thread contract breach) run under the monitor,
# no jax programs, <1s.  Exit 1 means the detector CAUGHT both (the
# pass condition here); anything else means it is blind or broken and
# must not be trusted to gate.
rc=0
JAX_PLATFORMS=cpu python -m dask_ml_tpu.sanitize.locks \
  --inject-inversion --inject-cross-write >/dev/null 2>&1 || rc=$?
if [[ "$rc" != 1 ]]; then
  echo "graftlock: seeded-fault self-test FAILED (exit $rc, want 1:" \
       "the lockset detector is blind)" >&2
  exit 1
fi
echo "graftlock: 2/2 seeded faults detected"

echo "== graftpilot (controller self-test: seeded false verdict must move) =="
# always on the default path, same posture as graftlock above: <1s, no
# jax programs.  The injected false-verdict must MOVE the readers knob
# AND synthetic saturation must FREEZE the controller.  NOTE the exit
# convention differs from graftlock's: here 0 means the controller is
# LIVE (both halves verified), and a disabled controller
# (DASK_ML_TPU_AUTOPILOT=off) exits 1 — it cannot vouch for itself, so
# it can never gate.
rc=0
JAX_PLATFORMS=cpu python -m dask_ml_tpu.control --self-test >/dev/null 2>&1 || rc=$?
if [[ "$rc" != 0 ]]; then
  echo "graftpilot: controller self-test FAILED (exit $rc, want 0:" \
       "the knob controller is blind or disabled)" >&2
  exit 1
fi
echo "graftpilot: false-verdict moved the knob + saturation froze it"

echo "== graftfleet (router self-test: seeded replica kill, zero lost) =="
# always on the default path, graftlock's exit convention: <1s, host-only
# models, no jax programs.  A replica is hard-killed mid-traffic; the
# sighted router must lose ZERO accepted requests and respawn the slot
# (exit 0).  Then the SAME kill runs through a BLIND router
# (DASK_ML_TPU_FLEET_INJECT=replica-kill: no readiness gate, no
# failover, no respawn) which MUST exit 1 — a zero-lost gate that
# cannot fail can never be trusted to gate.
rc=0
JAX_PLATFORMS=cpu python -m dask_ml_tpu.serve.fleet --self-test \
  >/dev/null 2>&1 || rc=$?
if [[ "$rc" != 0 ]]; then
  echo "graftfleet: self-test FAILED (exit $rc, want 0: the fleet lost" \
       "accepted requests across a replica kill)" >&2
  exit 1
fi
rc=0
JAX_PLATFORMS=cpu DASK_ML_TPU_FLEET_INJECT=replica-kill \
  python -m dask_ml_tpu.serve.fleet --self-test >/dev/null 2>&1 || rc=$?
if [[ "$rc" != 1 ]]; then
  echo "graftfleet: seeded-fault self-test FAILED (exit $rc, want 1:" \
       "a blind router lost nothing — the loss detector is broken)" >&2
  exit 1
fi
echo "graftfleet: zero lost across replica kill + blind router caught"

# (in --rebaseline mode the --write-baseline runs above already
# self-gated each fresh snapshot's hard invariants; --sanitize/--drills
# are the standalone gates against the committed ones)
if [[ "$SANITIZE" == 1 ]]; then
  echo "== graftsan (runtime sanitizer smoke suite vs $SAN_BASELINE) =="
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m dask_ml_tpu.sanitize --baseline "$SAN_BASELINE"
  echo "== grafttrace (obs smoke: tests/test_obs.py) =="
  # the observability spine's own suite rides the runtime smoke path:
  # span stitching, exporters, the overhead gate (records a block)
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/test_obs.py -q -p no:cacheprovider
fi

if [[ "$DRILLS" == 1 ]]; then
  echo "== graftdrill (chaos drill suite vs $DRILL_BASELINE) =="
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m dask_ml_tpu.resilience.drills --baseline "$DRILL_BASELINE"
fi

if [[ "$LOCKS" == 1 ]]; then
  echo "== graftlock (runtime lockset ratchet vs $LOCK_BASELINE) =="
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m dask_ml_tpu.sanitize.locks --baseline "$LOCK_BASELINE"
fi

if [[ "$CONTRACTS" == 1 ]]; then
  echo "== graftcontract (contract drift ratchet vs $CONTRACT_BASELINE) =="
  JAX_PLATFORMS=cpu python -m dask_ml_tpu.analysis dask_ml_tpu \
    --select "$CONTRACT_RULES" --baseline "$CONTRACT_BASELINE"
fi

echo "== compileall =="
python -m compileall -q dask_ml_tpu
echo "lint OK"
