#!/usr/bin/env bash
# End-to-end preemption drills for the launcher, four acts.  A CPU drill:
# act 3 runs two trainers at once and a chip belongs to one process at a
# time, so every trainer here runs with JAX_PLATFORMS=cpu.
#
# Act 1 -- SIGKILL (no notice):
#   1. start a real `python -m repro.launch.train --vcycle` run,
#   2. SIGKILL it as soon as the first checkpoint is published,
#   3. restart with identical args,
#   4. require the "[vcycle] resumed at phase=... level=... seg_step=..." line.
#
# Act 2 -- SIGTERM (preemption notice):
#   1. start a plain run whose --ckpt-every cadence can never fire,
#   2. SIGTERM it mid-training,
#   3. require exit 0, the "[preempt]" final BLOCKING checkpoint, and a
#      restart that resumes from exactly that save.
#
# Act 3 -- multi-process SIGTERM drain (cross-host preemption):
#   1. start a 2-process jax.distributed V-cycle run (localhost coordinator,
#      --mesh 2x1 spanning both processes, coordinated sharded checkpoints),
#   2. SIGTERM process 1 ONLY,
#   3. require BOTH processes to exit 0 with a "[preempt]" drain save at the
#      SAME global step (the notice propagates via an all-reduced flag),
#   4. restart as a SINGLE process and require the mid-V-cycle resume line
#      (checkpoints are process-count-elastic).
#
# Act 4 -- content-addressed local-dir store through the CLI:
#   1. run with --ckpt-local-dir (v3 object pool + manifests in a per-host
#      dir), SIGKILL after the first publish,
#   2. restart with identical args and require the mid-V-cycle resume line,
#   3. require the objects/ pool and a step manifest to actually exist.
#
# Exercises the whole path -- CLI, CheckpointManager atomic publish, VCycleState
# restore, PreemptionGuard -- not just the library functions (see also
# tests/test_system.py::test_vcycle_launcher_sigkill_resume,
# ::test_vcycle_launcher_sigterm_checkpoints and tests/test_multiprocess.py).
set -euo pipefail
cd "$(dirname "$0")/.."

CKPT=$(mktemp -d)
LOG=$(mktemp)
CKPT2=$(mktemp -d)
LOG2=$(mktemp)
CKPT3=$(mktemp -d)
LOG3A=$(mktemp)
LOG3B=$(mktemp)
CKPT4=$(mktemp -d)
LOG4=$(mktemp)
trap 'rm -rf "$CKPT" "$LOG" "$CKPT2" "$LOG2" "$CKPT3" "$LOG3A" "$LOG3B" "$CKPT4" "$LOG4"' EXIT
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export JAX_PLATFORMS=cpu

ARGS=(--arch tinyllama-1.1b --smoke --vcycle --levels 2 --steps 40
      --batch 2 --seq 16 --ckpt-dir "$CKPT" --ckpt-every 3)

python -m repro.launch.train "${ARGS[@]}" >"$LOG" 2>&1 &
PID=$!

# wait (up to ~4 min) for the first atomic checkpoint publish
for _ in $(seq 1 2400); do
  [ -f "$CKPT/manifest.json" ] && break
  kill -0 "$PID" 2>/dev/null || break
  sleep 0.1
done

if kill -0 "$PID" 2>/dev/null; then
  kill -9 "$PID"
  wait "$PID" 2>/dev/null || true
  echo "[smoke] SIGKILLed training after first checkpoint"
else
  echo "[smoke] WARNING: training exited before the kill; resume not exercised" >&2
fi

[ -f "$CKPT/manifest.json" ] || { echo "FAIL: no checkpoint was written"; tail -20 "$LOG"; exit 1; }

OUT=$(python -m repro.launch.train "${ARGS[@]}")
LINE=$(echo "$OUT" | grep -m1 "resumed at phase=") || {
  echo "FAIL: restart did not print the resume line"; echo "$OUT" | tail -20; exit 1; }
echo "PASS (act 1): $LINE"

# ----- Act 2: SIGTERM preemption-aware checkpoint ---------------------------
# cadence (10000) never fires within 300 steps: the ONLY way a checkpoint can
# exist is the SIGTERM handler's final blocking save
ARGS2=(--arch tinyllama-1.1b --smoke --steps 300 --batch 2 --seq 16
       --ckpt-dir "$CKPT2" --ckpt-every 10000)

python -m repro.launch.train "${ARGS2[@]}" >"$LOG2" 2>&1 &
PID2=$!

# wait (up to ~4 min) until training is demonstrably stepping
for _ in $(seq 1 2400); do
  grep -q "\[train\] step" "$LOG2" 2>/dev/null && break
  kill -0 "$PID2" 2>/dev/null || break
  sleep 0.1
done

kill -0 "$PID2" 2>/dev/null || {
  echo "FAIL: training exited before SIGTERM could be delivered"; tail -20 "$LOG2"; exit 1; }
kill -TERM "$PID2"
RC=0; wait "$PID2" || RC=$?
[ "$RC" -eq 0 ] || { echo "FAIL: SIGTERM exit code $RC (want clean 0)"; tail -20 "$LOG2"; exit 1; }
grep -q "\[preempt\] SIGTERM: final checkpoint" "$LOG2" || {
  echo "FAIL: no preemption checkpoint line"; tail -20 "$LOG2"; exit 1; }
[ -f "$CKPT2/manifest.json" ] || { echo "FAIL: SIGTERM wrote no checkpoint"; exit 1; }

OUT2=$(python -m repro.launch.train "${ARGS2[@]}")
LINE2=$(echo "$OUT2" | grep -m1 "resumed from step") || {
  echo "FAIL: restart did not resume from the preemption save"; echo "$OUT2" | tail -20; exit 1; }
echo "PASS (act 2): $LINE2"

# ----- Act 3: 2-process coordinated SIGTERM drain + 1-process resume --------
PORT=$(python -c "import socket; s=socket.socket(); s.bind(('127.0.0.1',0)); print(s.getsockname()[1]); s.close()")
ARGS3=(--arch tinyllama-1.1b --smoke --vcycle --levels 2 --steps 40
       --batch 4 --seq 16 --f32 --ckpt-dir "$CKPT3" --ckpt-every 1000)
MP=(--mesh 2x1 --coordinator "127.0.0.1:$PORT" --num-processes 2)

python -m repro.launch.train "${ARGS3[@]}" "${MP[@]}" --process-id 0 >"$LOG3A" 2>&1 &
PID3A=$!
python -m repro.launch.train "${ARGS3[@]}" "${MP[@]}" --process-id 1 >"$LOG3B" 2>&1 &
PID3B=$!

# wait (up to ~4 min) until the cycle is demonstrably past the first segment
for _ in $(seq 1 2400); do
  grep -q "coalescing" "$LOG3A" 2>/dev/null && break
  kill -0 "$PID3A" 2>/dev/null || break
  sleep 0.1
done
kill -0 "$PID3A" 2>/dev/null && kill -0 "$PID3B" 2>/dev/null || {
  echo "FAIL: a process died before SIGTERM could be delivered"
  tail -20 "$LOG3A"; tail -20 "$LOG3B"; exit 1; }

kill -TERM "$PID3B"  # ONE process gets the preemption notice...
RCA=0; RCB=0
wait "$PID3A" || RCA=$?
wait "$PID3B" || RCB=$?
[ "$RCA" -eq 0 ] && [ "$RCB" -eq 0 ] || {
  echo "FAIL: drain exits were rc=$RCA/rc=$RCB (want 0/0)"
  tail -20 "$LOG3A"; tail -20 "$LOG3B"; exit 1; }
# ...and BOTH drain through the same final-save step
STEP_A=$(grep -o "blocking V-cycle checkpoint at global_step [0-9]*" "$LOG3A" | grep -o "[0-9]*$")
STEP_B=$(grep -o "blocking V-cycle checkpoint at global_step [0-9]*" "$LOG3B" | grep -o "[0-9]*$")
[ -n "$STEP_A" ] && [ "$STEP_A" = "$STEP_B" ] || {
  echo "FAIL: drain steps disagree ('$STEP_A' vs '$STEP_B')"
  tail -20 "$LOG3A"; tail -20 "$LOG3B"; exit 1; }
[ -f "$CKPT3/manifest.json" ] || { echo "FAIL: drain wrote no checkpoint"; exit 1; }

OUT3=$(python -m repro.launch.train "${ARGS3[@]}")   # single process, no mesh
LINE3=$(echo "$OUT3" | grep -m1 "resumed at phase=") || {
  echo "FAIL: single-process restart did not resume the 2-process save"
  echo "$OUT3" | tail -20; exit 1; }
echo "PASS (act 3): both processes drained at step $STEP_A; $LINE3"

# ----- Act 4: --ckpt-local-dir (content-addressed per-host store) -----------
ARGS4=(--arch tinyllama-1.1b --smoke --vcycle --levels 2 --steps 40
       --batch 2 --seq 16 --ckpt-local-dir "$CKPT4" --ckpt-every 3)

python -m repro.launch.train "${ARGS4[@]}" >"$LOG4" 2>&1 &
PID4=$!

for _ in $(seq 1 2400); do
  [ -f "$CKPT4/manifest.json" ] && break
  kill -0 "$PID4" 2>/dev/null || break
  sleep 0.1
done

if kill -0 "$PID4" 2>/dev/null; then
  kill -9 "$PID4"
  wait "$PID4" 2>/dev/null || true
  echo "[smoke] SIGKILLed local-dir training after first checkpoint"
fi
[ -f "$CKPT4/manifest.json" ] || { echo "FAIL: local-dir wrote no checkpoint"; tail -20 "$LOG4"; exit 1; }
[ -d "$CKPT4/objects" ] || { echo "FAIL: no content-addressed object pool"; ls "$CKPT4"; exit 1; }
ls "$CKPT4"/step_*/objects.json >/dev/null 2>&1 || {
  echo "FAIL: no v3 step manifest"; ls -R "$CKPT4" | head -30; exit 1; }

OUT4=$(python -m repro.launch.train "${ARGS4[@]}")
LINE4=$(echo "$OUT4" | grep -m1 "resumed at phase=") || {
  echo "FAIL: restart did not resume from the local-dir store"; echo "$OUT4" | tail -20; exit 1; }
echo "PASS (act 4): $LINE4"
