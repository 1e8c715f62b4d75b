"""Runner ``serve_open``: open-loop region requests over TCP against an
in-process ``ServeLoop`` behind ``make_tcp_server`` (default config).

Traffic parameters: ``sites`` (a reviewer's site list, seeded positions on
the file's span), ``widths_bp``, ``zipf_s``, ``tenants`` (one connection
each: admission blocks a connection's reader, as it would one client's),
``rate_per_s`` (fixed; found once by a sweep, see PERF.md), ``warm`` (build
every tile in set-up and check that a second pass misses none).  The load
comes from ``loadgen.py`` in a child process that never imports JAX.  Every
reply's count is compared with the NumPy reference's region count.
"""
from __future__ import annotations

import json
import os
import socket
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import gen, loadgen

_WARM_BATCH = 16     # regions to a warm-up request


def setup(ctx) -> None:
    from hadoop_bam_tpu.formats.bam import SAMHeader
    from hadoop_bam_tpu.serve import ServeLoop
    from hadoop_bam_tpu.serve.transport import make_tcp_server
    from hadoop_bam_tpu.write import write_bam_records

    ctx.ref = gen.Reference(needs=("regions",))
    ctx.bam = os.path.join(ctx.workdir, "sample.bam")
    n_chunks, chunk = ctx.sizes["chunks"], ctx.sizes["chunk_records"]
    gen.write_sorted_bam(ctx.bam, ctx.seed, n_chunks, chunk, ctx.ref,
                         write_bam_records, SAMHeader.from_sam_text,
                         workers=ctx.gen_workers)
    ctx.part_done("generate+write")

    # the reviewer's site list and every region a request can name
    widths = [int(w) for w in ctx.param("widths_bp")]
    n_sites = int(ctx.param("sites"))
    rng = np.random.default_rng([ctx.seed, 106])
    sites = rng.integers(max(widths), gen.CONTIG_LEN - max(widths), n_sites)
    ctx.regions, ctx.want = [], []
    for s in sites:
        row_r, row_w = [], []
        for w in widths:
            lo = int(s) - w // 2
            row_r.append(f"{gen.CONTIG}:{lo}-{lo + w - 1}")
            row_w.append(ctx.ref.region_count(lo, lo + w - 1))
        ctx.regions.append(row_r)
        ctx.want.append(row_w)
    ctx.part_done("reference")

    ctx.loop = ServeLoop().start()
    ctx.server = make_tcp_server(ctx.loop)
    ctx.server_thread = threading.Thread(target=ctx.server.serve_forever,
                                         name="bench-tcp", daemon=True)
    ctx.server_thread.start()
    ctx.addr = ctx.server.server_address[:2]

    # one seed offers one schedule, so set-up knows which regions the
    # window will name and warms those and no others
    ctx.plan = _plan(ctx, float(ctx.param("rate_per_s")), ctx.seconds)
    if ctx.param("warm"):
        named = sorted({(s, w) for _due, s, w, _t in ctx.plan})
        if ctx.args.sweep:          # a sweep's schedules name any region
            named = [(s, w) for s in range(n_sites)
                     for w in range(len(widths))]
        flat = [(ctx.regions[s][w], ctx.want[s][w]) for s, w in named]
        _warm_pass(ctx, flat, _WARM_BATCH)              # builds the tiles
        misses = _warm_pass(ctx, flat[::4], 1)          # the request shape
        tiles = ctx.loop.stats()["tiles"]
        ctx.say(f"warm-up: the schedule names {len(flat)} of "
                f"{n_sites * len(widths)} regions; they built "
                f"{tiles['entries']} tiles, {tiles['bytes'] / 1e6:.1f} MB "
                f"of {tiles['byte_budget'] / 1e6:.0f} MB, evictions "
                f"{tiles['evictions']}; second pass over {len(flat[::4])} "
                f"single-region requests missed {misses} tiles")
        if misses or tiles["evictions"]:
            raise RuntimeError("the warm cell's tiles are not all resident "
                               "after set-up")
    ctx.part_done("warm-up")


def _warm_pass(ctx, flat, batch: int) -> int:
    """Ask for ``flat`` regions ``batch`` to a request on one connection,
    check every count, and return the tile misses the replies report."""
    misses = 0
    with socket.create_connection(ctx.addr, timeout=300) as sock:
        rf = sock.makefile("r")
        for i in range(0, len(flat), batch):
            part = flat[i:i + batch]
            sock.sendall((json.dumps({
                "id": i, "path": ctx.bam, "tenant": "warmup",
                "regions": [r for r, _w in part]}) + "\n").encode())
            ans = json.loads(rf.readline())
            if "results" not in ans:
                raise RuntimeError(f"warm-up request failed: {ans}")
            got = [r["count"] for r in ans["results"]]
            if got != [w for _r, w in part]:
                raise RuntimeError(f"warm-up counts {got} != reference "
                                   f"{[w for _r, w in part]}")
            misses += sum(r["tile_misses"] for r in ans["results"])
    return misses


def _plan(ctx, rate: float, seconds: float) -> list:
    params = dict(ctx.traffic["params"], rate_per_s=rate)
    return loadgen.schedule(params, ctx.seed, seconds, len(ctx.regions))


def _offer(ctx, plan: list, tag: str) -> dict:
    """One open-loop schedule through the child generator."""
    params = ctx.traffic["params"]
    seconds = plan[-1][0]
    tenants = [f"reviewer-{t}" for t in range(int(params["tenants"]))]
    sched = {"connections": tenants, "requests": [
        [due, t, {"path": ctx.bam, "tenant": tenants[t],
                  "regions": [ctx.regions[s][w]]}]
        for due, s, w, t in plan]}
    sched_path = os.path.join(ctx.workdir, f"schedule-{tag}.json")
    out_path = os.path.join(ctx.workdir, f"answers-{tag}.json")
    with open(sched_path, "w", encoding="utf-8") as fh:
        json.dump(sched, fh)
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(loadgen.__file__),
                                      "loadgen.py"),
         str(ctx.addr[0]), str(ctx.addr[1]), sched_path, out_path])
    try:
        rc = child.wait(timeout=seconds + loadgen.DRAIN_S + 60)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        raise
    if rc != 0:
        raise RuntimeError(f"load generator exited {rc}")
    with open(out_path, encoding="utf-8") as fh:
        res = json.load(fh)
    wrong = failed = 0
    lat, late = [], []
    for (due, s, w, _t), rec in zip(plan, res["requests"]):
        late.append(rec["late"])
        if rec.get("counts") is None or rec["latency"] is None:
            failed += 1
            continue
        lat.append(rec["latency"])
        wrong += rec["counts"] != [ctx.want[s][w]]
    res.update(n=len(plan), failed=failed, wrong=wrong, lat=lat, late=late,
               errors=sorted({r["error"] for r in res["requests"]
                              if "error" in r})[:3])
    return res


def _pct(values, p: int) -> float:
    return statistics.quantiles(values, n=100, method="inclusive")[p - 1]


def measure(ctx) -> dict:
    rate = float(ctx.param("rate_per_s"))
    r = _offer(ctx, ctx.plan, "window")
    lat = r["lat"]
    n_ok = len(lat)
    ctx.say(f"offered {r['n']} requests in {r['offered_s']:.3f} s "
            f"({r['n'] / max(r['offered_s'], 1e-9):.2f}/s, schedule rate "
            f"{rate}/s); completed {n_ok} in {r['wall_s']:.3f} s "
            f"({n_ok / r['wall_s']:.2f}/s); failed {r['failed']} "
            f"{r['errors']}; wrong counts {r['wrong']}")
    e2e = {}
    if n_ok >= 2:
        e2e = {"serve_p50_ms": 1e3 * _pct(lat, 50),
               "serve_p95_ms": 1e3 * _pct(lat, 95)}
        ctx.say(f"latency from the due instant over {n_ok} samples: p50 "
                f"{e2e['serve_p50_ms']:.3f} ms, p95 "
                f"{e2e['serve_p95_ms']:.3f} ms ({n_ok // 20} samples "
                f"beyond it), p99 {1e3 * _pct(lat, 99):.3f} ms, max "
                f"{1e3 * max(lat):.3f} ms; generator late p99 "
                f"{1e3 * _pct(r['late'], 99):.3f} ms max "
                f"{1e3 * max(r['late']):.3f} ms")
    return {"correct": r["wrong"] == 0 and n_ok > 0, "attempted": r["n"],
            "failed": r["failed"], "end_to_end": e2e,
            "observations": {"lateness_s": r["late"], "latency_s": lat,
                             "units": {"requests": n_ok}}}


def sweep(ctx, rates, seconds: float) -> None:
    """Stepped rates in one process, a few seconds each: the knee is the
    highest rate whose backlog does not grow (late-half latency about the
    early half's, every request answered)."""
    ctx.say("sweep: rate/s offered completed/s failed p50_ms p95_ms "
            "first_half_p50_ms second_half_p50_ms")
    for k, rate in enumerate(rates):
        r = _offer(ctx, _plan(ctx, float(rate), seconds), f"sweep{k}")
        lat = r["lat"]
        half = len(lat) // 2
        ctx.say("sweep: %.1f %d %.2f %d %.2f %.2f %.2f %.2f" % (
            rate, r["n"], len(lat) / r["wall_s"], r["failed"],
            1e3 * _pct(lat, 50), 1e3 * _pct(lat, 95),
            1e3 * statistics.median(lat[:half]),
            1e3 * statistics.median(lat[half:])))


def teardown(ctx) -> None:
    server = getattr(ctx, "server", None)
    if server is not None:
        server.shutdown()
        server.server_close()
        ctx.server_thread.join(timeout=30)
    loop = getattr(ctx, "loop", None)
    if loop is not None:
        loop.stop()
