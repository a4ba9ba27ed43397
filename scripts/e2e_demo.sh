#!/usr/bin/env bash
# End-to-end walkthrough of the full reference workflow on synthetic data:
#
#   train -> mean-vector -> publish -> serve -> query load -> online SGD
#   (closed loop) -> MSE against the live model
#
# mirroring the reference's operational pipeline (SURVEY.md §3): ALSImpl ->
# ALSMeanVector -> ALSKafkaProducer -> ALSKafkaConsumer -> ALSPredictRandom
# -> SGD -> MSE, with the journal standing in for the Kafka topic and the
# lookup server for Flink queryable state.
#
# Usage: scripts/e2e_demo.sh [workdir]    (defaults to a fresh mktemp dir)
# Runs where jax puts it: on the chip when there is one (the trainer exits
# before the serving job takes the chip; every later step is a JAX-free
# client), and on a host without one only when asked:
#   JAX_PLATFORMS=cpu scripts/e2e_demo.sh
set -euo pipefail
cd "$(dirname "$0")/.."

WORK=${1:-$(mktemp -d /tmp/flink-ms-tpu-demo.XXXXXX)}
mkdir -p "$WORK"
PY=${PYTHON:-python}
PORT=${PORT:-16123}
JOB_ID=demo-$$

echo "== workspace: $WORK  (serving on 127.0.0.1:$PORT, job $JOB_ID)"

echo "== [1/8] synthetic ratings (50 users x 40 items, 2000 ratings)"
$PY - "$WORK" <<'PYEOF'
import sys, numpy as np
work = sys.argv[1]
rng = np.random.default_rng(42)
n = 2000
users = rng.integers(0, 50, n)
items = rng.integers(0, 40, n)
# low-rank ground truth so training + online updates have signal
uf = rng.normal(size=(50, 4)); vf = rng.normal(size=(40, 4))
ratings = (uf[users] * vf[items]).sum(1) + rng.normal(scale=0.1, size=n)
with open(f"{work}/ratings.tsv", "w") as f:
    f.write("user\titem\trating\n")
    for u, i, r in zip(users, items, ratings):
        f.write(f"{u}\t{i}\t{r:.4f}\n")
# a later batch of "fresh" ratings for the online-SGD update stream
m = 500
uu = rng.integers(0, 50, m); ii = rng.integers(0, 40, m)
rr = (uf[uu] * vf[ii]).sum(1) + rng.normal(scale=0.1, size=m)
with open(f"{work}/updates.tsv", "w") as f:
    for u, i, r in zip(uu, ii, rr):
        f.write(f"{u}\t{i}\t{r:.4f}\n")
PYEOF

echo "== [2/8] batch ALS training (als_train ~ ALSImpl)"
$PY -m flink_ms_tpu.train.als_train \
  --input "$WORK/ratings.tsv" --fieldDelimiter tab --ignoreFirstLine true \
  --iterations 5 --numFactors 8 --lambda 0.1 \
  --userFactors "$WORK/model/userFactors" --itemFactors "$WORK/model/itemFactors"

echo "== [3/8] cold-start mean vectors (mean_vector ~ ALSMeanVector)"
$PY -m flink_ms_tpu.eval.mean_vector --type user \
  --input "$WORK/model/userFactors" --output "$WORK/model/meanU"
$PY -m flink_ms_tpu.eval.mean_vector --type item \
  --input "$WORK/model/itemFactors" --output "$WORK/model/meanI"

echo "== [4/8] publish model rows into the journal (als_producer ~ ALSKafkaProducer)"
$PY -m flink_ms_tpu.serve.als_producer \
  --input "$WORK/model" --journalDir "$WORK/journal" --topic als-model

echo "== [5/8] serving job (als_consumer ~ ALSKafkaConsumer) in background"
$PY -m flink_ms_tpu.serve.als_consumer \
  --journalDir "$WORK/journal" --topic als-model \
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
sleep 2   # let the ingest thread drain the topic into the model table

echo "== [6/8] random-query latency harness (als_predict_random ~ ALSPredictRandom)"
$PY -m flink_ms_tpu.client.als_predict_random \
  --jobId "$JOB_ID" --jobManagerHost 127.0.0.1 --jobManagerPort "$PORT" \
  --numQueries 200 --lowerUserId 0 --upperUserId 49 \
  --lowerItemId 0 --upperItemId 39 --outputFile "$WORK/latency.csv"
echo "   latency csv head:"; head -3 "$WORK/latency.csv" | sed 's/^/     /'

echo "== [7/8] MSE against the live served model, before online updates"
$PY -m flink_ms_tpu.eval.mse --input "$WORK/ratings.tsv" \
  --jobId "$JOB_ID" --jobManagerHost 127.0.0.1 --jobManagerPort "$PORT" \
  --output "$WORK/mse_before.txt"

echo "== [8/8] online SGD on fresh ratings (sgd ~ SGD.java), closing the loop"
$PY -m flink_ms_tpu.online.sgd \
  --input "$WORK/updates.tsv" --mode once --outputMode kafka \
  --journalDir "$WORK/journal" --topic als-model \
  --jobId "$JOB_ID" --jobManagerHost 127.0.0.1 --jobManagerPort "$PORT" \
  --learningRate 0.05
sleep 2   # serving job folds the updated rows back into the state

$PY -m flink_ms_tpu.eval.mse --input "$WORK/ratings.tsv" \
  --jobId "$JOB_ID" --jobManagerHost 127.0.0.1 --jobManagerPort "$PORT" \
  --output "$WORK/mse_after.txt"

echo "== done"
echo "   MSE before online updates: $(cat "$WORK/mse_before.txt")"
echo "   MSE after  online updates: $(cat "$WORK/mse_after.txt")"
echo "   artifacts under $WORK (model/, journal/, ckpt/, latency.csv, serving.log)"
