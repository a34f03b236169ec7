#!/usr/bin/env bash
# Multi-process protocol deployment (reference server.py + N client.py
# parity): one broker, one server, three clients, over real TCP sockets.
# CPU by choice: an accelerator belongs to one process, and these are
# five.  (How the protocol path gets one chip for each process is
# ROADMAP S0/S3; until then it is CPU-only.)
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
CFG=${1:-examples/quickstart_tcp.yaml}

python -m split_learning_tpu.broker --port 5699 &
trap 'kill $(jobs -p) 2>/dev/null || true' EXIT
sleep 1

python -m split_learning_tpu.client --config "$CFG" --layer_id 1 --client_id edge_a &
python -m split_learning_tpu.client --config "$CFG" --layer_id 1 --client_id edge_b &
python -m split_learning_tpu.client --config "$CFG" --layer_id 2 --client_id head &

python -m split_learning_tpu.server --config "$CFG"
wait
