#!/usr/bin/env bash
# End-to-end SVM workflow on synthetic data, mirroring the reference's
# pipeline (SURVEY.md §3): SVMImpl (CoCoA training, range-partitioned
# output) -> SVMKafkaProducer -> SVMKafkaConsumer -> SVMPredictRandom and
# RangePartitionSVMPredict latency harnesses.
#
# Usage: scripts/e2e_demo_svm.sh [workdir]
# Runs where jax puts it; on a host without a chip only when asked:
#   JAX_PLATFORMS=cpu scripts/e2e_demo_svm.sh
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${1:-$(mktemp -d /tmp/flink-ms-tpu-svm-demo.XXXXXX)}
mkdir -p "$WORK"
PY=${PYTHON:-python}
PORT=${PORT:-16124}
JOB_ID=svm-demo-$$
N_FEATURES=200
RANGE=50

echo "== workspace: $WORK  (serving on 127.0.0.1:$PORT, job $JOB_ID)"

echo "== [1/6] synthetic LibSVM training data (1000 x $N_FEATURES, separable)"
$PY - "$WORK" "$N_FEATURES" <<'PYEOF'
import sys, numpy as np
work, n_feat = sys.argv[1], int(sys.argv[2])
rng = np.random.default_rng(42)
w_true = rng.normal(size=n_feat)
with open(f"{work}/train.libsvm", "w") as f:
    for _ in range(1000):
        nnz = rng.integers(5, 20)
        idx = np.sort(rng.choice(n_feat, size=nnz, replace=False))
        val = rng.normal(size=nnz)
        label = 1 if val @ w_true[idx] > 0 else -1
        f.write(f"{label} " + " ".join(
            f"{i + 1}:{v:.4f}" for i, v in zip(idx, val)) + "\n")
PYEOF

echo "== [2/6] CoCoA SVM training, range-partitioned output (svm_train ~ SVMImpl)"
$PY -m flink_ms_tpu.train.svm_train \
  --training "$WORK/train.libsvm" --blocks 4 --iteration 10 \
  --partition true --range "$RANGE" --output "$WORK/model/weights"

echo "== [3/6] publish weight rows into the journal (svm_producer ~ SVMKafkaProducer)"
$PY -m flink_ms_tpu.serve.svm_producer \
  --input "$WORK/model" --journalDir "$WORK/journal" --topic svm-model

echo "== [4/6] serving job (svm_consumer ~ SVMKafkaConsumer) in background"
$PY -m flink_ms_tpu.serve.svm_consumer \
  --journalDir "$WORK/journal" --topic svm-model \
  --stateBackend fs --checkpointDataUri "$WORK/ckpt" \
  --host 127.0.0.1 --port "$PORT" --jobId "$JOB_ID" \
  >"$WORK/serving.log" 2>&1 &
SERVE_PID=$!
trap 'kill $SERVE_PID 2>/dev/null || true' EXIT

$PY - "$PORT" <<'PYEOF'
import socket, sys, time
port = int(sys.argv[1])
deadline = time.time() + 60
while time.time() < deadline:
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=2) as s:
            s.sendall(b"PING\n")
            if s.recv(64).startswith(b"PONG"):
                sys.exit(0)
    except OSError:
        time.sleep(0.3)
sys.exit("serving job did not come up")
PYEOF
sleep 2

echo "== [5/6] query-per-bucket latency harness (range_partition_svm_predict)"
$PY -m flink_ms_tpu.client.range_partition_svm_predict \
  --jobId "$JOB_ID" --jobManagerHost 127.0.0.1 --jobManagerPort "$PORT" \
  --numQueries 200 --maxNoOfFeatures "$N_FEATURES" --range "$RANGE" \
  --outputFile "$WORK/latency_bucket.csv"
echo "   bucket-query latency csv head:"; head -3 "$WORK/latency_bucket.csv" | sed 's/^/     /'

echo "== [6/6] done"
echo "   artifacts under $WORK (model/, journal/, ckpt/, latency_bucket.csv)"
