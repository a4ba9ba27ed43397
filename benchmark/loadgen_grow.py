"""The two load generators of a catalog that grows while it is read, each a
child process that never imports jax.

    python3 -m benchmark.loadgen_grow <spec.json>

`"role": "reads"` is `benchmark/loadgen_mix.py`'s reading loop (TOPKV on a
constant-gap schedule dealt round-robin over the connections, none with two
in flight, latency from the intended send time, EVERY reply kept) with one
difference: request n asks pool slot `slots[n]`, a file the parent made from
the seed by the latest law (`benchmark/synth_grow.py`), not `n mod pool`.
Replies go to `<out>.replies.txt`, one line a request in request order.

`"role": "writes"` is `benchmark/loadgen_mix.py`'s one writer as it stands:
it appends the rows of `updates` (here each the row of a NEW id) to the
serving job's topic on its own constant-gap schedule, half a read gap after
the reads', one `Journal.append([row], flush=False)` a row, and logs the
intended instant, the call and the return of each.

Both are told the instant the window opens on stdin; perf_counter is
CLOCK_MONOTONIC, one clock for every process.
"""

from __future__ import annotations

import json
import selectors
import sys
import time

import numpy as np

from benchmark import synth
from benchmark.loadgen import Conn
from benchmark.loadgen_mix import sleep_until, writes


def reads(spec):
    k = spec["k"]
    vectors = synth.queries(spec["seed"], spec["pool"], spec["rank"])
    lines = [
        f"TOPKV\t{spec['state']}\t{k}\t{synth.query_payload(v)}\n".encode()
        for v in vectors
    ]
    slots = np.load(spec["slots"])
    conns = []
    for _ in range(spec["connections"]):
        conns.append(Conn(spec["host"], spec["port"]))
        time.sleep(0.005)  # the server's listen backlog is 5: do not outrun accept
    sel = selectors.DefaultSelector()
    for c in conns:
        sel.register(c.sock, selectors.EVENT_READ, c)

    print("ready", flush=True)
    t_open = float(sys.stdin.readline())
    t0 = t_open - spec["lead_s"]            # load starts before the window
    t_close = t_open + spec["seconds"]
    t_give_up = t_close + spec["drain_s"]
    n_max = min(len(slots), int(np.ceil((t_close - t0) * spec["rate_per_s"])))
    schedule = t0 + np.arange(n_max) / spec["rate_per_s"]
    sent = np.zeros(n_max)
    done = np.zeros(n_max)
    ok = np.zeros(n_max, np.int8)
    replies = [""] * n_max
    n = 0

    def receive(conn):
        chunk = conn.sock.recv(1 << 20)
        now = time.perf_counter()
        if not chunk:
            raise ConnectionError("lookup server closed the connection")
        conn.buf += chunk
        while True:
            nl = conn.buf.find(b"\n")
            if nl < 0:
                return
            reply = bytes(conn.buf[:nl])
            del conn.buf[:nl + 1]
            i = conn.out.popleft()
            done[i] = now
            ok[i] = reply.startswith(b"V\t") and reply.count(b";") == k - 1
            replies[i] = reply.decode()

    sleep_until(t0)
    while True:
        now = time.perf_counter()
        outstanding = any(c.out for c in conns)
        if now >= t_give_up or (now >= t_close and not outstanding):
            break
        if n < n_max and now >= schedule[n]:
            conn = conns[n % len(conns)]
            sent[n] = time.perf_counter()
            conn.sock.sendall(lines[slots[n]])
            conn.out.append(n)
            n += 1
            continue
        wait = t_give_up - now
        if n < n_max:
            wait = min(wait, schedule[n] - now)
        for key, _ in sel.select(max(wait, 0.0)):
            receive(key.data)
    for c in conns:
        c.sock.close()
    np.savez(spec["out"], intended=schedule[:n], sent=sent[:n], done=done[:n],
             ok=ok[:n])
    with open(spec["out"] + ".replies.txt", "w") as f:
        f.write("".join(r + "\n" for r in replies[:n]))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)
    {"reads": reads, "writes": writes}[spec["role"]](spec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
