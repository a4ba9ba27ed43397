"""The load generator of independent arrivals: a child process that never
imports jax.

    python3 -m benchmark.loadgen_poisson <spec.json>

`benchmark/loadgen.py` (the lookup server's frozen v1 tab protocol over TCP,
one thread, one `selectors` loop, one raw record per request: intended send
time, actual send time, time the reply line was read, ok; requests dealt
round-robin over the connections, pool vectors cycled in order, latency from
the intended send time) with ONE difference: the schedule is a Poisson
process of mean `rate_per_s`, exponential gaps drawn from the seed, not a
constant gap.  `loadgen.py` builds its schedule inline and a PR may not edit
it, hence this file.  A connection's turns come a sum of `connections` gaps
apart (64 at 600 /s: 107 ms, sd 13), so none has two requests in flight
unless a reply takes five times a frame.

The spec is written by the parent (`benchmark/drivers/topk_open_poisson.py`)
from the traffic file.  The parent names the instant the window opens on
stdin; perf_counter is CLOCK_MONOTONIC, one clock for both processes.
"""

from __future__ import annotations

import json
import selectors
import sys
import time

import numpy as np

from benchmark import synth
from benchmark.loadgen import Conn


def arrivals(seed, rate_per_s, t0, t_close):
    """The instants of a Poisson process of mean `rate_per_s` from `t0`
    until `t_close`, from the seed: exponential gaps (half as many again as
    the mean needs, and six sd, cover the span)."""
    mean = (t_close - t0) * rate_per_s
    gaps = np.random.default_rng([seed, 11]).exponential(
        1.0 / rate_per_s, int(np.ceil(1.5 * mean + 6 * mean ** 0.5)) + 1)
    at = t0 + np.cumsum(gaps)
    return at[at < t_close]


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
    schedule = arrivals(spec["seed"], spec["rate_per_s"], t0, t_close)
    n_max = len(schedule)
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
