"""Extended serving soak: the suite's 4s soak run for ~15 minutes with the
NATIVE (rocksdb-parity) backend and repeated process-loss/restart cycles.
Exits 0 iff no reader/writer errors and every key serves after each
restart."""
import os
import socket
import sys
import tempfile
import threading
import time

sys.path.insert(0, "/root/repo")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.core.params import Params
from flink_ms_tpu.online import sgd as online_sgd
from flink_ms_tpu.serve.client import QueryClient
from flink_ms_tpu.serve.consumer import (
    ALS_STATE, ServingJob, make_backend, parse_als_record,
)
from flink_ms_tpu.serve.journal import Journal

DURATION_S = float(os.environ.get("SOAK_S", 900))
RESTART_EVERY_S = float(os.environ.get("SOAK_RESTART_S", 180))

rng = np.random.default_rng(0)
k, n_users, n_items = 8, 200, 300
td = tempfile.mkdtemp(prefix="long_soak_")
bus = os.path.join(td, "bus")
j = Journal(bus, "m", segment_bytes=1 << 16, retain_segments=256)
rows = [F.format_als_row(i, t, rng.normal(size=k))
        for t in ("U", "I") for i in range(n_users if t == "U" else n_items)]
rows += ["MEAN,U," + ";".join(["0.0"] * k),
         "MEAN,I," + ";".join(["0.0"] * k)]
j.append(rows, flush=True)
chk = os.path.join(td, "chk")


def wait_until(pred, timeout=60.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.05)
    return False


def start_job():
    job = ServingJob(
        Journal(bus, "m"), ALS_STATE, parse_als_record,
        make_backend("rocksdb", chk), host="127.0.0.1", port=0,
        poll_interval_s=0.01, checkpoint_interval_ms=500,
    ).start()
    return job


job = start_job()
assert wait_until(lambda: len(job.table) >= len(rows)), "initial ingest"

stop = threading.Event()
errors: list = []
reads = {"mget": 0, "topk": 0}
port_lock = threading.Lock()
current_port = [job.port]


def sgd_writer():
    ratings = os.path.join(td, "ratings.tsv")
    recs = [(int(rng.integers(0, n_users)), int(rng.integers(0, n_items)),
             float(rng.uniform(1, 5))) for _ in range(200_000)]
    with open(ratings, "w") as f:
        f.write("".join(f"{u}\t{i}\t{r}\n" for u, i, r in recs))
    while not stop.is_set():
        with port_lock:
            port = current_port[0]
        try:
            online_sgd.run(Params.from_dict({
                "input": ratings, "mode": "continuous", "interval": 20,
                "outputMode": "journal", "journalDir": bus, "topic": "m",
                "jobId": job.job_id, "jobManagerHost": "127.0.0.1",
                "jobManagerPort": port, "queryTimeout": 30,
                "batchSize": 16, "flushEveryUpdate": False,
            }), stop=stop.is_set)
        except Exception as e:  # noqa: BLE001
            # a mid-restart connection error is expected; anything else is a
            # soak failure.  Match by TYPE: ConnectionError covers
            # BrokenPipeError/ConnectionResetError/ConnectionRefusedError
            # (a repr-substring check missed BrokenPipeError, whose repr
            # carries no "Connection"), socket.timeout covers a send into a
            # half-torn-down server.
            expected = isinstance(e, (ConnectionError, socket.timeout))
            if not stop.is_set() and not expected:
                errors.append(f"sgd: {e!r}")
                return
            time.sleep(0.5)


def reader(kind):
    while not stop.is_set():
        with port_lock:
            port = current_port[0]
        try:
            with QueryClient("127.0.0.1", port, timeout_s=30) as c:
                for _ in range(100):
                    if stop.is_set():
                        return
                    u = int(rng.integers(0, n_users))
                    i = int(rng.integers(0, n_items))
                    if kind == "mget":
                        ps = c.query_states(ALS_STATE, [f"{u}-U", f"{i}-I"])
                        assert len(ps) == 2
                        reads["mget"] += 1
                    else:
                        res = c.topk(ALS_STATE, str(u), 5)
                        assert res is None or len(res) <= 5
                        reads["topk"] += 1
        except Exception as e:  # noqa: BLE001
            msg = repr(e)
            if not stop.is_set() and "Connection" not in msg \
                    and "refused" not in msg and "reset" not in msg.lower():
                errors.append(f"{kind}: {msg}")
                return
            time.sleep(0.2)


threads = [threading.Thread(target=sgd_writer, daemon=True),
           threading.Thread(target=reader, args=("mget",), daemon=True),
           threading.Thread(target=reader, args=("topk",), daemon=True)]
for t in threads:
    t.start()

t_end = time.time() + DURATION_S
restarts = 0
while time.time() < t_end and not errors:
    time.sleep(min(RESTART_EVERY_S, max(t_end - time.time(), 1)))
    if time.time() >= t_end:
        break
    # process loss mid-soak: stop without final flush, restart, verify
    job.stop()
    job = start_job()
    with port_lock:
        current_port[0] = job.port
    end = Journal(bus, "m").end_offset()
    ok = wait_until(lambda: job.offset >= end, timeout=120)
    if not ok:
        errors.append(f"restart {restarts}: replay stalled at "
                      f"{job.offset}/{end}")
        break
    with QueryClient("127.0.0.1", job.port, timeout_s=30) as c:
        for u in range(0, n_users, 17):
            if c.query_state(ALS_STATE, f"{u}-U") is None:
                errors.append(f"restart {restarts}: missing key {u}-U")
                break
    restarts += 1
    print(f"[soak] restart {restarts} ok at t+{DURATION_S - (t_end - time.time()):.0f}s, "
          f"reads={reads}", flush=True)

stop.set()
for t in threads:
    t.join(timeout=60)
job.stop()
print(f"[soak] done: restarts={restarts}, reads={reads}, errors={errors}",
      flush=True)
sys.exit(1 if errors else 0)
