#!/usr/bin/env python3
"""Open-loop load generator: a child process that never imports JAX.

    python3 benchmark/loadgen.py <host> <port> <schedule.json> <out.json>

``schedule.json`` holds ``{"connections": [tenant, ...], "requests":
[[due_s, connection index, request doc], ...]}`` in due order.  One sender
thread sends each request at its due instant whatever the server is doing
(an open loop: a stalled server does not slow the offered load), one
receiver thread reads every connection's replies.  A latency runs from the
instant the request was DUE, so a late generator or a stalled server both
count; how late each send was is reported beside it.  Stdlib only: the
generator shares neither the GIL nor the chip with the server.

``schedule()`` builds that list in the parent from the traffic file's
parameters and ``--seed`` alone.
"""
from __future__ import annotations

import json
import selectors
import socket
import sys
import threading
import time

DRAIN_S = 30.0      # how long unanswered requests are waited for at the end


def schedule(params: dict, seed: int, seconds: float, n_sites: int) -> list:
    """[(due_s, site rank, width index, tenant index)] for one run.

    Every seed offers the SAME arrivals and the same multiset of sites,
    widths and tenants: gaps are the n stratified quantiles of the
    exponential distribution at ``rate_per_s`` (so they sum to ~n / rate)
    in an order fixed by the traffic file's ``arrival_seed``; sites get
    their Zipf(s) share of the n requests by largest remainder, widths
    and tenants go round-robin, and ``--seed`` decides which request
    carries which.  A seed then changes which regions meet which bursts
    (and the data under them), not how much work is offered or when."""
    import numpy as np

    rate = float(params["rate_per_s"])
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(np.random.default_rng(
        int(params["arrival_seed"])).permutation(gaps))
    rng = np.random.default_rng([int(seed), 4242])
    w = 1.0 / np.arange(1, n_sites + 1) ** float(params["zipf_s"])
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(share - counts), kind="stable")[:short]] += 1
    sites = rng.permutation(np.repeat(np.arange(n_sites), counts))
    widths = rng.permutation(np.arange(n) % len(params["widths_bp"]))
    tenants = rng.permutation(np.arange(n) % int(params["tenants"]))
    return [(float(due[i]), int(sites[i]), int(widths[i]), int(tenants[i]))
            for i in range(n)]


def run(host: str, port: int, sched: dict) -> dict:
    conns = [socket.create_connection((host, port), timeout=120)
             for _ in sched["connections"]]
    for c in conns:
        c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    reqs = sched["requests"]
    n = len(reqs)
    sent = [None] * n
    recv = [None] * n
    answers = [None] * n
    lines = [(json.dumps(dict(doc, id=i)) + "\n").encode()
             for i, (_due, _c, doc) in enumerate(reqs)]
    left = threading.Semaphore(0)
    stop = threading.Event()

    def receiver() -> None:
        sel = selectors.DefaultSelector()
        bufs = {}
        for c in conns:
            sel.register(c, selectors.EVENT_READ)
            bufs[c] = b""
        while not stop.is_set():
            for key, _ev in sel.select(timeout=0.2):
                c = key.fileobj
                try:
                    data = c.recv(1 << 16)
                except OSError:
                    data = b""
                if not data:
                    sel.unregister(c)
                    continue
                now = time.perf_counter()
                bufs[c] += data
                *whole, bufs[c] = bufs[c].split(b"\n")
                for raw in whole:
                    ans = json.loads(raw)
                    i = ans.get("id")
                    if isinstance(i, int) and 0 <= i < n \
                            and recv[i] is None:
                        recv[i] = now
                        answers[i] = ans
                        left.release()

    rx = threading.Thread(target=receiver, name="loadgen-rx", daemon=True)
    rx.start()
    t0 = time.perf_counter() + 0.05
    for i, (due, c, _doc) in enumerate(reqs):
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        conns[c].sendall(lines[i])
    t_last_due = t0 + (reqs[-1][0] if reqs else 0.0)
    deadline = time.perf_counter() + DRAIN_S
    for _ in range(n):
        if not left.acquire(timeout=max(0.0, deadline
                                        - time.perf_counter())):
            break
    t_end = time.perf_counter()
    stop.set()
    rx.join(timeout=5)
    for c in conns:
        c.close()
    out = []
    for i, (due, _c, _doc) in enumerate(reqs):
        ans = answers[i]
        rec = {"due": due, "late": sent[i] - (t0 + due),
               "latency": None if recv[i] is None
               else recv[i] - (t0 + due)}
        if ans is not None and "results" in ans:
            rec["counts"] = [r["count"] for r in ans["results"]]
            rec["tile_misses"] = sum(r["tile_misses"]
                                     for r in ans["results"])
        elif ans is not None:
            rec["error"] = f"{ans.get('kind')}: {ans.get('error')}"
        out.append(rec)
    return {"requests": out, "offered_s": t_last_due - t0,
            "wall_s": t_end - t0}


def main(argv) -> int:
    host, port, sched_path, out_path = argv
    with open(sched_path, encoding="utf-8") as fh:
        sched = json.load(fh)
    res = run(host, int(port), sched)
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(res, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
