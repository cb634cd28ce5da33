#!/usr/bin/env bash
# Two-process DCN data-plane dryrun (round 19): two REAL JAX CPU
# processes under jax.distributed — rechunk parity on the hierarchical
# `dcn` schedule, the sharded-bundle load barrier (including the
# poisoned-shard typed abort), and a coherent cross-process
# shrink→grow capacity episode.  See tools/mh_dryrun.py for the phases.
#
# The rechunk collective phase is also proven on every tier-1 run through
# the single-process DSLIB_MOCK_HOSTS overlay
# (tests/test_multihost_dataplane.py).
#
# --chaos (round 20) runs the process-killing survival drill instead:
# ``tools/mh_dryrun.py --chaos`` SIGKILLs one of two real coordinated
# processes mid-fit, restarts it, delays heartbeats, tears coordination/
# ledger files, and kills it again at the load barrier — green means the
# survivor's resumed model matches the shrunk-fleet oracle, the rejoin
# grows back under a bumped epoch, every abort is typed, and nothing
# hangs (the driver hard-bounds every wait).
#
#   tools/run_multihost.sh [--chaos]
cd "$(dirname "$0")/.." || exit 1

if [ "$1" = "--chaos" ]; then
  LOG=$(mktemp)
  env JAX_PLATFORMS=cpu timeout -k 10 600 \
      python tools/mh_dryrun.py --chaos 2>&1 | tee "$LOG"
  rc=${PIPESTATUS[0]}
  if [ "$rc" -eq 0 ] && grep -q "MULTIHOST CHAOS: PASS" "$LOG"; then
    rm -f "$LOG"; exit 0
  fi
  rm -f "$LOG"
  echo "MULTIHOST CHAOS: FAIL (rc=$rc)"
  exit 1
fi

WORK=$(mktemp -d)
trap 'rm -rf "$WORK"' EXIT
PORT=$(python - <<'EOF'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0))
print(s.getsockname()[1]); s.close()
EOF
)

echo "-- launching 2 ranks (coordinator 127.0.0.1:$PORT, work $WORK) --"
pids=()
for r in 0 1; do
  env -u XLA_FLAGS -u JAX_PLATFORMS \
      timeout -k 10 300 \
      python tools/mh_dryrun.py "$r" 2 "$PORT" "$WORK" \
      > "$WORK/rank$r.log" 2>&1 &
  pids+=($!)
done

rc=0
for i in 0 1; do
  if ! wait "${pids[$i]}"; then rc=1; fi
done
for r in 0 1; do
  echo "-- rank $r --"
  cat "$WORK/rank$r.log"
done
if [ $rc -eq 0 ] && grep -q "ALL PHASES GREEN" "$WORK/rank0.log" \
   && grep -q "ALL PHASES GREEN" "$WORK/rank1.log"; then
  echo "MULTIHOST DRYRUN: PASS"
else
  echo "MULTIHOST DRYRUN: FAIL"
  exit 1
fi
