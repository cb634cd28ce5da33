#!/usr/bin/env bash
# Chaos soak (round-8 satellite): N randomized-schedule fault-injection
# fit runs — preemption + NaN-in-carry + hung chunk + snapshot corruption
# combined — asserting the resilience+health invariant (self-heal or a
# typed diagnostic, then a clean resume equals the unfaulted model).
#
# Usage:  tools/chaos_soak.sh [RUNS] [SEED]
#         tools/chaos_soak.sh --matrix [SEED] [OUT_JSONL]
#         tools/chaos_soak.sh --oscillate [SEED]
#         tools/chaos_soak.sh --trainer [SEED] [OUT_JSONL]
#         tools/chaos_soak.sh --multihost [SEED] [OUT_JSONL]
#
# Default mode runs the `slow`-marked tests/test_chaos_soak.py (excluded
# from tier-1) and echoes the machine-readable summary line.  The modes
# that take OUT_JSONL also APPEND their summary line to that file when it
# is given; there is no default file.
#
# --matrix (round-12) runs the seeded chaos MATRIX instead — every
# chunked estimator × every fault injector incl. the tier-targeted
# FaultAtTier (tests/test_chaos_matrix.py) — and APPENDS its
# machine-readable summary (per-cell verdicts + resilience counters) to
# OUT_JSONL as one JSON line.
#
# --oscillate (round-16) runs the oscillating-CAPACITY tier: a seeded
# shrink → heal → grow device-availability walk across every chunked
# estimator family, asserting zero consumed rollback budget and an
# oracle-matching model after every swing (bidirectional elasticity).
#
# --trainer (round-17) runs the CONTINUOUS-LEARNING soak: one
# ContinuousTrainer driven train → bundle → canary → promote through six
# generations with a fault at every seam (torn export, corrupt bundle,
# canary gate trip, preemption, capacity shrink/grow, explicit rollback)
# while client threads decode (tenant, generation) from every response —
# and APPENDS the summary to OUT_JSONL.
# --multihost (round-20) runs the MULTI-HOST SURVIVAL soak: repeated
# kill → resume → rejoin → grow-back episodes (lease-based membership
# over a FileCoordinator, death published as a capacity level, the
# head-home grow on rejoin) under live retrieval client traffic — every
# dead-window failure must be TYPED (ShardDrained), the healed model
# must equal the unfaulted oracle, and the rank_deaths/rank_rejoins
# counters are asserted per episode.  APPENDS the summary to OUT_JSONL.
set -o pipefail
cd "$(dirname "$0")/.." || exit 1
if [ "$1" = "--multihost" ]; then
    SEED="${2:-0}"
    OUT="${3:-}"
    LOG="$(mktemp)"
    env JAX_PLATFORMS=cpu DSLIB_SOAK_SEED="$SEED" \
        timeout -k 10 900 \
        python -m pytest tests/test_chaos_soak.py::test_chaos_mh_soak \
        -q -m slow -s -p no:cacheprovider 2>&1 | tee "$LOG"
    rc=${PIPESTATUS[0]}
    echo "-- multihost soak summary --"
    grep -a "^CHAOS_MH_SUMMARY" "$LOG" | sed 's/^CHAOS_MH_SUMMARY //'
    if [ "$rc" -eq 0 ] && [ -n "$OUT" ]; then
        grep -a "^CHAOS_MH_SUMMARY" "$LOG" \
            | sed 's/^CHAOS_MH_SUMMARY //' >> "$OUT"
        echo "appended to $OUT"
    fi
    rm -f "$LOG"
    exit $rc
fi
if [ "$1" = "--trainer" ]; then
    SEED="${2:-0}"
    OUT="${3:-}"
    LOG="$(mktemp)"
    env JAX_PLATFORMS=cpu DSLIB_SOAK_SEED="$SEED" \
        python -m pytest tests/test_chaos_soak.py::test_chaos_trainer_soak \
        -q -m slow -s -p no:cacheprovider 2>&1 | tee "$LOG"
    rc=${PIPESTATUS[0]}
    echo "-- trainer soak summary --"
    grep -a "^CHAOS_TRAINER_SUMMARY" "$LOG" | sed 's/^CHAOS_TRAINER_SUMMARY //'
    if [ "$rc" -eq 0 ] && [ -n "$OUT" ]; then
        grep -a "^CHAOS_TRAINER_SUMMARY" "$LOG" \
            | sed 's/^CHAOS_TRAINER_SUMMARY //' >> "$OUT"
        echo "appended to $OUT"
    fi
    rm -f "$LOG"
    exit $rc
fi
if [ "$1" = "--oscillate" ]; then
    SEED="${2:-0}"
    LOG="$(mktemp)"
    env JAX_PLATFORMS=cpu DSLIB_SOAK_SEED="$SEED" \
        python -m pytest \
        tests/test_chaos_soak.py::test_chaos_oscillation_soak \
        -q -m slow -s -p no:cacheprovider 2>&1 | tee "$LOG"
    rc=${PIPESTATUS[0]}
    echo "-- oscillation summary --"
    grep -a "^CHAOS_OSC_SUMMARY" "$LOG" | sed 's/^CHAOS_OSC_SUMMARY //'
    rm -f "$LOG"
    exit $rc
fi
if [ "$1" = "--matrix" ]; then
    SEED="${2:-0}"
    OUT="${3:-}"
    LOG="$(mktemp)"
    env JAX_PLATFORMS=cpu DSLIB_MATRIX_SEED="$SEED" \
        python -m pytest tests/test_chaos_matrix.py::test_chaos_matrix_full \
        -q -m slow -s -p no:cacheprovider 2>&1 | tee "$LOG"
    rc=${PIPESTATUS[0]}
    echo "-- matrix summary --"
    grep -a "^CHAOS_MATRIX_SUMMARY" "$LOG" | sed 's/^CHAOS_MATRIX_SUMMARY //'
    if [ "$rc" -eq 0 ] && [ -n "$OUT" ]; then
        grep -a "^CHAOS_MATRIX_SUMMARY" "$LOG" \
            | sed 's/^CHAOS_MATRIX_SUMMARY //' >> "$OUT"
        echo "appended to $OUT"
    fi
    rm -f "$LOG"
    exit $rc
fi
RUNS="${1:-10}"
SEED="${2:-0}"
LOG="$(mktemp)"
env JAX_PLATFORMS=cpu DSLIB_SOAK_RUNS="$RUNS" DSLIB_SOAK_SEED="$SEED" \
    python -m pytest tests/test_chaos_soak.py -q -m slow -s \
    -p no:cacheprovider 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo "-- soak summary --"
grep -a "^CHAOS_SOAK_SUMMARY" "$LOG" | sed 's/^CHAOS_SOAK_SUMMARY //'
rm -f "$LOG"
exit $rc
