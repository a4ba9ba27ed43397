"""The load generator: a child process that never imports jax.

It speaks the lookup server's frozen v1 tab protocol over TCP (one request
line, one reply line, replies in request order on a connection), from one
thread and one `selectors` loop, and writes one raw record per request:
intended send time, actual send time, time the reply line was read, ok.

    python3 -m benchmark.loadgen <spec.json>

The spec is written by the parent (`benchmark/drivers/topk_open.py`) from the
traffic file: open loop, a constant-gap schedule at `rate_per_s` dealt
round-robin over the connections, latency from the intended send time.  The
parent names the instant the window opens on stdin; perf_counter is
CLOCK_MONOTONIC, one clock for both processes.
"""

from __future__ import annotations

import json
import selectors
import socket
import sys
import time
from collections import deque

import numpy as np

from benchmark import synth


class Conn:
    def __init__(self, host, port):
        self.sock = socket.create_connection((host, port), 10.0)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(True)
        self.buf = bytearray()
        self.out = deque()  # request indices awaiting their reply, in order


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        spec = json.load(f)
    k, pool = spec["k"], spec["pool"]
    vectors = synth.queries(spec["seed"], pool, spec["rank"])
    lines = [
        f"TOPKV\t{spec['state']}\t{k}\t{synth.query_payload(v)}\n".encode()
        for v in vectors
    ]
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
    n_max = int(np.ceil((t_close - t0) * spec["rate_per_s"]))
    schedule = t0 + np.arange(n_max) / spec["rate_per_s"]
    intended = np.zeros(n_max)
    sent = np.zeros(n_max)
    done = np.zeros(n_max)
    ok = np.zeros(n_max, np.int8)
    replies = {}
    n = 0

    def send(conn):
        nonlocal n
        intended[n] = schedule[n]
        sent[n] = time.perf_counter()
        conn.sock.sendall(lines[n % pool])
        conn.out.append(n)
        n += 1

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
            replies[i % pool] = reply.decode()

    while time.perf_counter() < t0:
        time.sleep(min(0.0005, max(0.0, t0 - time.perf_counter())))
    while True:
        now = time.perf_counter()
        outstanding = any(c.out for c in conns)
        if now >= t_give_up or (now >= t_close and not outstanding):
            break
        if n < n_max and now >= schedule[n]:
            send(conns[n % len(conns)])
            continue
        wait = t_give_up - now
        if n < n_max:
            wait = min(wait, schedule[n] - now)
        for key, _ in sel.select(max(wait, 0.0)):
            receive(key.data)
    for c in conns:
        c.sock.close()
    np.savez(spec["out"], intended=intended[:n], sent=sent[:n], done=done[:n],
             ok=ok[:n])
    with open(spec["out"] + ".replies.json", "w") as f:
        json.dump(replies, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
