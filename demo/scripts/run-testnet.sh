#!/usr/bin/env bash
# Start N babble nodes + N dummy chat clients on localhost — reference
# demo/scripts/run-testnet.sh without the containers. PIDs land in
# demo/conf/pids for stop.sh.
set -euo pipefail
cd "$(dirname "$0")/../.."
NODES="${NODES:-4}" BASE_PORT="${BASE_PORT:-22000}"
HEARTBEAT="${HEARTBEAT:-50}" ENGINE="${ENGINE:-host}" CONF="demo/conf"
# d > 1: shard engine state over d devices (requires ENGINE=tpu;
# ignored by the host engine)
ENGINE_MESH="${ENGINE_MESH:-0}"
[ -d "$CONF/node0" ] || { echo "run conf.sh first" >&2; exit 1; }
# A chip belongs to the one process that initializes JAX on it: a second
# --engine tpu process on this host would fail or hang waiting for it.
# Run one tpu node per host (chip_smoke.py runs several validators on
# one chip inside a single process).
if [ "$ENGINE" = "tpu" ] && [ "$NODES" -gt 1 ]; then
  echo "ENGINE=tpu needs one chip per node process; refusing $NODES" \
       "nodes on this host (use NODES=1, or ENGINE=host)" >&2
  exit 1
fi
: > "$CONF/pids"
for i in $(seq 0 $((NODES - 1))); do
  p=$((BASE_PORT + i * 10))
  python -m babble_tpu.cli run \
    --datadir "$CONF/node$i" \
    --node_addr "127.0.0.1:$p" \
    --proxy_addr "127.0.0.1:$((p + 1))" \
    --client_addr "127.0.0.1:$((p + 2))" \
    --service_addr "127.0.0.1:$((BASE_PORT + 1000 + i))" \
    --heartbeat "$HEARTBEAT" --engine "$ENGINE" \
    --engine_mesh "$ENGINE_MESH" --log_level info \
    >"$CONF/logs/node$i.log" 2>&1 &
  echo $! >> "$CONF/pids"
  python -m babble_tpu.dummy --name "client$i" \
    --node_addr "127.0.0.1:$((p + 1))" \
    --client_addr "127.0.0.1:$((p + 2))" \
    --log "$CONF/logs/messages$i.txt" \
    </dev/null >"$CONF/logs/client$i.log" 2>&1 &
  echo $! >> "$CONF/pids"
done
echo "testnet up: $NODES nodes; /Stats on ports $((BASE_PORT + 1000)).."
