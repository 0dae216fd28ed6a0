#!/bin/bash
# Shared helpers for the orchestration scripts.
#
# The inter-script contract (same as the reference tooling): phases hand off
# through files on a shared filesystem; a consumer polls until its input
# appears (inotifywait when present, 1s sleep otherwise); producers write to
# a temp name and atomically mv into place; phase durations are echoed as
# "<Phase> in <seconds> seconds." which the make-parallel harness greps.

# Block until $1 exists, watching directory $2 for creations.
# (|| true: an inotifywait poll timeout is not a failure — the sourcing
# driver runs under set -e.)
sheep_wait_for() {
  local target="$1" watch_dir="$2"
  while [ ! -f "$target" ]; do
    if [ "${USE_INOTIFY:-1}" = "0" ]; then
      inotifywait -qqt 1 -e create -e moved_to "$watch_dir" || true
    else
      sleep 1
    fi
  done
}

# Reap every PID given; non-zero if ANY failed.  The phase drivers use
# this instead of a bare `wait` so a crashed worker aborts the run (under
# the driver's set -e) instead of the next phase silently merging fewer
# trees.
sheep_wait_all() {
  local rc=0 pid
  for pid in "$@"; do
    if ! wait "$pid"; then
      echo "worker (pid $pid) failed" >&2
      rc=1
    fi
  done
  return $rc
}

# Rename an artifact together with its .sum sidecar (integrity layer,
# ISSUE 2).  Sidecar moves FIRST so a polling consumer that sees the
# artifact under its final name also sees the matching checksum — the
# reverse order would leave a window where the artifact reads as
# unverified (or worse, pairs with a stale sidecar).
sheep_mv_artifact() {
  local src="$1" dst="$2"
  [ -f "$src.sum" ] && mv "$src.sum" "$dst.sum"
  mv "$src" "$dst"
}

# Heartbeat emission (supervisor liveness contract, sheep_tpu/supervisor/
# heartbeat.py): touch $1 every SHEEP_HEARTBEAT_S (default 1) seconds from
# a background loop.  The beat is the file's mtime — same protocol the
# Python workers speak — and the loop self-terminates when this shell
# dies (kill -0 $$), so a SIGKILLed worker goes silent instead of an
# orphaned loop beating on its behalf forever.
sheep_heartbeat_start() {
  local hb="$1"
  [ -z "$hb" ] && return 0
  (
    while kill -0 $$ 2>/dev/null; do
      touch "$hb" 2>/dev/null || exit 0
      sleep "${SHEEP_HEARTBEAT_S:-1}"
    done
  ) &
  SHEEP_HB_PID=$!
  return 0
}

# Stop the beat loop started by sheep_heartbeat_start (a clean worker
# exit; death is covered by the loop's kill -0 self-check).
sheep_heartbeat_stop() {
  if [ -n "${SHEEP_HB_PID:-}" ]; then
    kill "$SHEEP_HB_PID" 2>/dev/null || true
    wait "$SHEEP_HB_PID" 2>/dev/null || true
    SHEEP_HB_PID=''
  fi
  return 0
}

# Nanosecond wall clock.
sheep_now() { date +%s%N; }

# Seconds (8 decimal places) between two sheep_now readings.
sheep_elapsed() {
  awk -v b="$1" -v e="$2" 'BEGIN{printf "%.8f", (e - b) / 1000000000}'
}

# Echo the per-worker banner when -v is active.
sheep_banner() {
  [ "$VERBOSE" = "-v" ] && echo "$1: $(hostname)"
  return 0
}

# Launch graph2tree on the mesh path.  By default ONE process runs the
# SPMD program over all local devices.  With SHEEP_PROCS > 1 this is the
# mpiexec analog: that many processes join one jax.distributed mesh via
# the SHEEP_COORDINATOR contract (process 0 owns all prints and writes).
# All of them run on this host, and a process that reaches the TPU claims
# every local chip, so the second would fail or hang: SHEEP_PROCS > 1 is
# refused unless JAX_PLATFORMS=cpu (the host-device emulation the tests
# use).
sheep_mesh_graph2tree() {
  local procs="${SHEEP_PROCS:-1}"
  if [ "$procs" -gt 1 ] && [ "${JAX_PLATFORMS:-}" != "cpu" ]; then
    echo "SHEEP_PROCS=$procs would start $procs graph2tree processes on" \
         "this host, and each would claim every local chip; unset" \
         "SHEEP_PROCS to run one process over all local chips (or set" \
         "JAX_PLATFORMS=cpu for the host-device emulation)" >&2
    return 2
  fi
  if [ "$procs" -gt 1 ]; then
    local port p pids='' rc=0
    # an OS-assigned free port, not a blind pick from the ephemeral range
    port=$(python -c 'import socket;s=socket.socket();s.bind(("127.0.0.1",0));print(s.getsockname()[1])')
    for p in $(seq 0 $(( procs - 1 ))); do
      SHEEP_COORDINATOR="127.0.0.1:$port" SHEEP_NUM_PROCESSES="$procs" \
        SHEEP_PROCESS_ID="$p" "$SHEEP_BIN/graph2tree" "$@" &
      pids="$pids $!"
    done
    # Fail fast like the mpiexec this emulates: one rank down kills the
    # job — survivors would otherwise block in collectives for minutes.
    # Poll OUR pids only (kill -0, then reap with an explicit wait PID) so
    # an unrelated background job of the sourcing shell is never miscounted
    # as a rank exit — bare `wait -n` reaps ANY job, and `wait -n PID...`
    # misses already-exited jobs on bash < 5.3.
    local pid remaining
    while [ -n "${pids// /}" ]; do
      remaining=''
      for pid in $pids; do
        if kill -0 "$pid" 2>/dev/null; then
          remaining="$remaining $pid"
        elif ! wait "$pid"; then
          rc=1
          kill $pids 2>/dev/null || true
        fi
      done
      pids="$remaining"
      [ -n "${pids// /}" ] && sleep 0.2
    done
    return $rc
  fi
  "$SHEEP_BIN/graph2tree" "$@"
}
