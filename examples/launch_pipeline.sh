#!/usr/bin/env bash
# Multi-worker pipeline launch recipe — the reference's docker-compose
# topology (2 workers + coordinator on one machine) as a plain script.
# Reference: /root/reference/docker-compose.yml (sync / semi-async profiles,
# cpuset-pinned workers). On real deployments run each line on its own host
# (or taskset/cgroup-pin them like the reference's cpuset stanzas).
#
# This script runs on the CPU only. It starts N workers plus a coordinator,
# every one a JAX process, on ONE host; a TPU chip belongs to one process at a
# time, so with a TPU platform the first process would take every chip and
# the rest would fail or hang. It therefore refuses any JAX_PLATFORMS other
# than cpu and pins nothing; for TPU stages run one worker per host.
#
# Usage: ./launch_pipeline.sh [num_workers] [schedule] [model]
set -euo pipefail
cd "$(dirname "$0")/.."

if [ "${JAX_PLATFORMS:-cpu}" != "cpu" ]; then
  echo "launch_pipeline.sh: JAX_PLATFORMS=$JAX_PLATFORMS refused: this script" \
       "starts several JAX processes on one host and a chip belongs to one" \
       "process; run it with JAX_PLATFORMS=cpu (or unset)" >&2
  exit 2
fi
export JAX_PLATFORMS=cpu

N=${1:-2}
SCHEDULE=${2:-semi_async}
MODEL=${3:-cifar10_cnn_v1}
BASE_PORT=${BASE_PORT:-9601}

PIDS=()
WORKERS=""
for i in $(seq 0 $((N - 1))); do
  PORT=$((BASE_PORT + i))
  python examples/network_worker.py --port "$PORT" &
  PIDS+=($!)
  WORKERS+="${WORKERS:+,}127.0.0.1:$PORT"
done
trap 'kill "${PIDS[@]}" 2>/dev/null || true' EXIT

WORKERS=$WORKERS SCHEDULE=$SCHEDULE MODEL=$MODEL \
  EPOCHS=${EPOCHS:-2} python examples/distributed_trainer.py
