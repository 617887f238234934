#!/bin/bash
# Shared helpers for the shell e2e tier (SURVEY.md §2.1 #31; reference
# test/lib.sh:36-57 boots N real server processes on random ports and the
# client pipes TOML configs between subcommands).
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="$REPO_ROOT${PYTHONPATH:+:$PYTHONPATH}"
# keep e2e on CPU so it never contends with TPU benchmarks; pin the fast
# unoptimized CPU codegen (the crypto graphs otherwise compile for ages and
# the auto-detected ISA has SIGILL'd — see tests/conftest.py)
export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_cpu_max_isa=AVX2 --xla_backend_optimization_level=0"

SERVER="python -m drynx_tpu.cmd.server"
CLIENT="python -m drynx_tpu.cmd.client"

WORKDIR="$(mktemp -d)"
declare -a SERVER_PIDS=()

cleanup() {
    for pid in "${SERVER_PIDS[@]:-}"; do
        kill "$pid" 2>/dev/null || true
    done
    wait 2>/dev/null || true
    rm -rf "$WORKDIR"
}
trap cleanup EXIT

random_port() {
    python - <<'EOF'
import socket
s = socket.socket()
s.bind(("127.0.0.1", 0))
print(s.getsockname()[1])
s.close()
EOF
}

# gen_node <name> -> writes $WORKDIR/<name>.toml, echoes "host:port"
gen_node() {
    local name="$1" port
    port="$(random_port)"
    $SERVER gen --address "127.0.0.1:$port" --name "$name" \
        > "$WORKDIR/$name.toml"
    echo "127.0.0.1:$port"
}

# start_node <name> [--data <file>] -> boots `server run` on its config
start_node() {
    local name="$1"; shift
    $SERVER run "$@" < "$WORKDIR/$name.toml" 2>"$WORKDIR/$name.log" &
    SERVER_PIDS+=("$!")
}

# node_public <name> -> "x,y" hex public key from the generated config
node_public() {
    python - "$WORKDIR/$1.toml" <<'EOF'
import sys
from drynx_tpu.cmd import toml_io
cfg = toml_io.loads(open(sys.argv[1]).read())["node"]
print(f"{cfg['public_x']},{cfg['public_y']}")
EOF
}

# wait_listening <name> — block until the node logs its listen line
wait_listening() {
    local name="$1" tries=0
    until grep -q "listening" "$WORKDIR/$name.log" 2>/dev/null; do
        tries=$((tries + 1))
        [ "$tries" -gt 300 ] && { echo "server $name never came up" >&2;
                                  cat "$WORKDIR/$name.log" >&2; return 1; }
        sleep 0.2
    done
}
