#!/usr/bin/env bash
# Train->serve soak drill through the real CLIs (a CPU drill: the trainer
# and the server run at the same time, and a chip belongs to one process at
# a time, so both run with JAX_PLATFORMS=cpu):
#
#   1. start a `python -m repro.launch.train --vcycle` run publishing a
#      checkpoint every 2 global steps,
#   2. wait for the first atomic manifest publish,
#   3. run `python -m repro.launch.serve --reload-from <ckpt-dir>` under
#      continuous traffic while the trainer keeps publishing,
#   4. require at least one live weight reload (the "[serve] reloads=N"
#      summary line) and ZERO dropped requests ("[serve] rejected req"
#      must not appear).
#
# Exercises the whole hand-off path -- trainer CLI, CheckpointManager atomic
# publish, ManifestWatcher digest-diff poll, EngineCore tick-boundary swap --
# not just the library functions (see also
# tests/test_system.py::test_serve_soak_live_trainer_reloads and
# tests/test_reload.py).
set -euo pipefail
cd "$(dirname "$0")/.."

CKPT=$(mktemp -d)
TLOG=$(mktemp)
SLOG=$(mktemp)
TPID=""
cleanup() {
  if [ -n "$TPID" ] && kill -0 "$TPID" 2>/dev/null; then
    kill -9 "$TPID" 2>/dev/null || true
    wait "$TPID" 2>/dev/null || true
  fi
  rm -rf "$CKPT" "$TLOG" "$SLOG"
}
trap cleanup EXIT
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export JAX_PLATFORMS=cpu

python -m repro.launch.train --arch tinyllama-1.1b --smoke --vcycle \
  --levels 2 --steps 40 --batch 2 --seq 16 \
  --ckpt-dir "$CKPT" --ckpt-every 2 >"$TLOG" 2>&1 &
TPID=$!

# wait (up to ~4 min) for the first atomic checkpoint publish
for _ in $(seq 1 2400); do
  [ -f "$CKPT/manifest.json" ] && break
  kill -0 "$TPID" 2>/dev/null || break
  sleep 0.1
done
[ -f "$CKPT/manifest.json" ] || {
  echo "FAIL: trainer never published a checkpoint"; tail -20 "$TLOG"; exit 1; }

# serve under traffic while the trainer keeps publishing into the same dir
python -m repro.launch.serve --arch tinyllama-1.1b --requests 24 --batch 4 \
  --max-new 8 --reload-from "$CKPT" >"$SLOG" 2>&1 || {
  echo "FAIL: serve exited nonzero"; tail -20 "$SLOG"; exit 1; }

if grep -q "rejected req" "$SLOG"; then
  echo "FAIL: server dropped requests during the soak"; tail -20 "$SLOG"; exit 1
fi
if ! grep -Eq "reloads=[1-9]" "$SLOG"; then
  echo "FAIL: no live weight reload happened"; tail -20 "$SLOG"; exit 1
fi
echo "PASS (serve soak): $(grep -m1 'reloads=' "$SLOG")"
