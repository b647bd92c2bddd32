#!/usr/bin/env bash
# 3-node localhost dev cluster (equivalent of reference
# script/dev-cluster.sh): three configs under /tmp/garage_tpu_dev, RPC on
# 3901/3911/3921, S3 on 3900/3910/3920, admin on 3903/3913/3923.
set -euo pipefail
cd "$(dirname "$0")/.."

BASE=${GARAGE_TPU_DEV_DIR:-/tmp/garage_tpu_dev}
SECRET=${GARAGE_TPU_RPC_SECRET:-dev-cluster-secret}
mkdir -p "$BASE"

for i in 0 1 2; do
  d="$BASE/node$i"
  mkdir -p "$d/meta" "$d/data"
  # a chip belongs to ONE process: node 0 keeps the default backend
  # (hybrid, attaches the device), nodes 1-2 run the CPU codec
  backend_line=""
  if [ "$i" != 0 ]; then backend_line='backend = "cpu"'; fi
  cat > "$d/garage.toml" <<EOF
metadata_dir = "$d/meta"
data_dir = "$d/data"
db_engine = "sqlite"
replication_mode = "3"
rpc_bind_addr = "127.0.0.1:39${i}1"
rpc_public_addr = "127.0.0.1:39${i}1"
rpc_secret = "$SECRET"
bootstrap_peers = ["127.0.0.1:3901", "127.0.0.1:3911", "127.0.0.1:3921"]

[s3_api]
s3_region = "garage"
api_bind_addr = "127.0.0.1:39${i}0"

[codec]
store_parity = true
$backend_line

[admin]
api_bind_addr = "127.0.0.1:39${i}3"
admin_token = "dev-admin-token"

[s3_web]
bind_addr = "127.0.0.1:39${i}2"
root_domain = ".web.garage.localhost"

[k2v_api]
api_bind_addr = "127.0.0.1:39${i}4"
EOF
  python -m garage_tpu -c "$d/garage.toml" server &
  echo "node$i pid $!"
done

sleep 2
echo "=== dev cluster up; configure with scripts/dev_configure.sh ==="
wait
