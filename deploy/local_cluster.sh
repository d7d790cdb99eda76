#!/usr/bin/env bash
# The compose topology without docker: store server + two service hosts
# as background processes (PID-file managed). `xdc` brings up TWO
# clusters wired as a replication group.
#
#   ./deploy/local_cluster.sh up [xdc]
#   ./deploy/local_cluster.sh status
#   ./deploy/local_cluster.sh down
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
RUN_DIR="${CADENCE_TPU_RUN_DIR:-/tmp/cadence_tpu_cluster}"
PIDS="$RUN_DIR/pids"
# A development topology: several service hosts on one machine, so it
# runs on XLA's CPU backend unless told otherwise (a chip belongs to one
# process — see README "Running"). Not a path any chip run takes.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

spawn() { # name cmd...
  local name="$1"; shift
  nohup "$@" >"$RUN_DIR/$name.log" 2>&1 &
  echo "$! $name" >> "$PIDS"
  echo "started $name (pid $!)"
}

wait_port() { # port
  for _ in $(seq 1 100); do
    python - "$1" <<'EOF' && return 0 || sleep 0.1
import socket, sys
s = socket.socket(); s.settimeout(0.2)
sys.exit(0 if s.connect_ex(("127.0.0.1", int(sys.argv[1]))) == 0 else 1)
EOF
  done
  echo "port $1 never listened" >&2; return 1
}

up() {
  mkdir -p "$RUN_DIR"
  if [ -f "$PIDS" ]; then
    while read -r pid name; do
      if kill -0 "$pid" 2>/dev/null; then
        echo "refusing: $name (pid $pid) still running — run down first" >&2
        exit 1
      fi
    done < "$PIDS"
  fi
  : > "$PIDS"
  spawn store python -m cadence_tpu.rpc.storeserver --port 7240 \
      --wal "$RUN_DIR/primary.wal"
  wait_port 7240
  local peer_args=()
  if [ "${1:-}" = "xdc" ]; then
    spawn store-standby python -m cadence_tpu.rpc.storeserver --port 7250 \
        --wal "$RUN_DIR/standby.wal"
    wait_port 7250
    peer_args=(--peer standby=127.0.0.1:7250)
    for i in 0 1; do
      spawn "standby-host-$i" python -m cadence_tpu.rpc.server \
          --name "standby-host-$i" --port "725$((i+1))" \
          --store 127.0.0.1:7250 --num-shards 16 \
          --cluster-name standby --peer primary=127.0.0.1:7240 \
          --http-port "825$((i+1))"
    done
  fi
  for i in 0 1; do
    spawn "host-$i" python -m cadence_tpu.rpc.server \
        --name "host-$i" --port "724$((i+1))" \
        --store 127.0.0.1:7240 --num-shards 16 \
        --cluster-name primary ${peer_args[@]+"${peer_args[@]}"} \
        --http-port "824$((i+1))"
  done
  wait_port 7241
  echo "cluster up: store 127.0.0.1:7240, frontends 7241/7242," \
       "scrape http://127.0.0.1:8241/metrics (logs in $RUN_DIR)"
}

down() {
  [ -f "$PIDS" ] || { echo "nothing running"; return 0; }
  while read -r pid name; do
    kill "$pid" 2>/dev/null && echo "stopped $name" || true
  done < "$PIDS"
  rm -f "$PIDS"
}

status() {
  [ -f "$PIDS" ] || { echo "nothing running"; return 0; }
  while read -r pid name; do
    if kill -0 "$pid" 2>/dev/null; then echo "$name: up (pid $pid)"
    else echo "$name: DEAD"; fi
  done < "$PIDS"
}

case "${1:-}" in
  up) up "${2:-}" ;;
  down) down ;;
  status) status ;;
  *) echo "usage: $0 up [xdc] | down | status" >&2; exit 2 ;;
esac
