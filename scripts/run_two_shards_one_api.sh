#!/usr/bin/env bash
# Local 2-shard + 1-API ring on one machine (reference:
# scripts/run_two_shards_one_api.sh — manual topology split across shards).
#
# Usage: scripts/run_two_shards_one_api.sh /path/to/model [layer_split]
#
# One process for each chip.  A process that has touched JAX owns every chip
# it can see, so three processes on one TPU host must not all see them all:
# the API node computes nothing in ring mode and runs on the CPU backend, and
# each shard is pinned to its own chip through libtpu's TPU_VISIBLE_CHIPS
# (with the process bounds that make one chip a whole topology).  Override
# SHARD0_CHIPS / SHARD1_CHIPS (e.g. "0,1" and "2,3" with
# SHARD_CHIP_BOUNDS=1,2,1) to give a shard more than one.  Off a TPU host the
# variables are inert; export JAX_PLATFORMS=cpu there as usual.
set -euo pipefail

MODEL="${1:?usage: $0 /path/to/model [split_layer]}"
SPLIT="${2:-}"
HERE="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$HERE"

S0_HTTP=8081; S0_GRPC=58081
S1_HTTP=8082; S1_GRPC=58082
API_HTTP=8080; API_GRPC=58080

NUM_LAYERS=$(python - "$MODEL" <<'EOF'
import json, sys, pathlib
print(json.loads((pathlib.Path(sys.argv[1]) / "config.json").read_text())["num_hidden_layers"])
EOF
)
SPLIT="${SPLIT:-$((NUM_LAYERS / 2))}"
echo ">> $NUM_LAYERS layers; shard0 = [0..$((SPLIT-1))], shard1 = [$SPLIT..$((NUM_LAYERS-1))]"

HOSTFILE="$(mktemp)"
cat > "$HOSTFILE" <<EOF
s0 127.0.0.1 $S0_HTTP $S0_GRPC
s1 127.0.0.1 $S1_HTTP $S1_GRPC
EOF

cleanup() { kill 0 2>/dev/null || true; }
trap cleanup EXIT

export TPU_PROCESS_BOUNDS=1,1,1
export TPU_CHIPS_PER_PROCESS_BOUNDS="${SHARD_CHIP_BOUNDS:-1,1,1}"
TPU_VISIBLE_CHIPS="${SHARD0_CHIPS:-0}" \
python -m dnet_tpu.cli.shard --host 127.0.0.1 --http-port $S0_HTTP --grpc-port $S0_GRPC \
    --shard-name s0 --discovery none &
TPU_VISIBLE_CHIPS="${SHARD1_CHIPS:-1}" \
python -m dnet_tpu.cli.shard --host 127.0.0.1 --http-port $S1_HTTP --grpc-port $S1_GRPC \
    --shard-name s1 --discovery none &
JAX_PLATFORMS=cpu \
python -m dnet_tpu.cli.api --host 127.0.0.1 --http-port $API_HTTP --grpc-port $API_GRPC \
    --hostfile "$HOSTFILE" &

for port in $S0_HTTP $S1_HTTP $API_HTTP; do
  until curl -sf "http://127.0.0.1:$port/health" > /dev/null; do sleep 0.5; done
done
echo ">> all nodes healthy"

LAYERS0=$(python -c "print(list(range(0, $SPLIT)))")
LAYERS1=$(python -c "print(list(range($SPLIT, $NUM_LAYERS)))")
curl -sf -X POST "http://127.0.0.1:$API_HTTP/v1/prepare_topology_manual" \
  -H 'Content-Type: application/json' \
  -d "{\"model\": \"$MODEL\", \"assignments\": [
        {\"instance\": \"s0\", \"layers\": $LAYERS0},
        {\"instance\": \"s1\", \"layers\": $LAYERS1}]}" | python -m json.tool
curl -sf -X POST "http://127.0.0.1:$API_HTTP/v1/load_model" \
  -H 'Content-Type: application/json' -d "{\"model\": \"$MODEL\"}" | python -m json.tool

echo ">> ring is serving; try:"
echo "curl -s http://127.0.0.1:$API_HTTP/v1/chat/completions -H 'Content-Type: application/json' \\"
echo "  -d '{\"model\":\"$MODEL\",\"messages\":[{\"role\":\"user\",\"content\":\"Hello\"}],\"max_tokens\":64}'"
wait
