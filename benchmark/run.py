#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is a new process.  It refuses any platform but a TPU, makes its data
from ``--seed``, warms up only the cell's own shapes (all of that is
``setup_s``), measures for ``--seconds``, checks its answers against the plain
NumPy reference, and prints ONE last line: the JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` in a
traced run).  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.  Everything else worth
reading goes on earlier lines.

Everything that belongs to one cell is found by name (see PERF.md, "Adding
to the benchmark"): ``BENCHMARK.json`` names the cell's configuration and
traffic mix; ``configs/<config>.json`` holds the sizes;
``traffic/<mix>.json`` names its runner (``runners/<kind>.py``) and its
parameters; ``layer_metrics/<metric>.json`` names a reducer
(``reducers/<module>.py``) and its parameters.

``--tiny`` is the explicit CPU rehearsal: it takes the configuration's
``tiny`` sizes and accepts ``JAX_PLATFORMS=cpu``; its last line says
``"platform": "cpu"`` and none of its numbers is a device's.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up starts with the process

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as fh:
        return json.load(fh)


class CompileWatch:
    """Counts the programs JAX compiled (or loaded from its persistent
    cache) and the seconds that took: none may fall inside the window."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1
                self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def state(self):
        with self._lock:
            return self.n, self.seconds, self.cache_hits, self.cache_misses


class Context:
    """What a runner is handed: the cell, its sizes and traffic
    parameters, a work directory that is removed on exit, and ``say`` for
    earlier lines."""

    def __init__(self, args, cell, config, traffic, workdir):
        self.args, self.cell, self.traffic = args, cell, traffic
        self.workdir = workdir
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.traced, self.tiny = bool(args.trace), bool(args.tiny)
        if self.traced and "trace_seconds" in traffic:
            # a profiler trace is held in memory: a traced run measures a
            # short window (30 s of scans ran a 40 GiB machine out of it)
            self.seconds = min(self.seconds, float(traffic["trace_seconds"]))
        # the configuration's sizes; the rehearsal's where asked for
        self.sizes = dict(config["sizes"], **(config["tiny"] if self.tiny
                                              else {}))
        # data is made in NumPy-only child processes beside the writer
        self.gen_workers = 1 if self.tiny else max(
            1, min((os.cpu_count() or 2) - 2, 12))
        self.parts: dict = {}          # set-up seconds by part
        self._t_part = T_START

    def say(self, msg: str) -> None:
        print(f"[{self.cell['name']}] {msg}", flush=True)

    def part_done(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self._t_part
        self._t_part = now

    def param(self, key: str):
        """A traffic parameter; the rehearsal's override where given."""
        if self.tiny and key in self.traffic.get("tiny", {}):
            return self.traffic["tiny"][key]
        return self.traffic["params"][key]


def device_doc(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def span_durations(recorder) -> dict:
    """{span name: [seconds]} of the program's own recorder, which is
    switched on just before the window of a traced run."""
    out: dict = {}
    if recorder is not None:
        for name, _ts, dur, *_rest in recorder.events():
            out.setdefault(name, []).append(dur)
    return out


def layer_metrics(bench: dict, cell: dict, obs: dict, say) -> dict:
    """Every per-layer metric of this cell that its reader can find."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = load_json("benchmark", "layer_metrics", m["name"] + ".json")
        mod, fn = spec["reducer"].split(".")
        reducer = getattr(importlib.import_module(
            f"benchmark.reducers.{mod}"), fn)
        value = reducer(spec.get("params", {}), obs)
        if value is None:
            say(f"per-layer {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="the explicit CPU rehearsal (JAX_PLATFORMS=cpu)")
    ap.add_argument("--bench", default="BENCHMARK.json",
                    help="the file of cells and metrics, relative to the "
                         "checkout: benchmark/candidates.json holds the "
                         "cells that are built but not yet proved")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: after set-up, hand them "
                         "to the runner's sweep(), --seconds each, and "
                         "print no result line (how a knee is found)")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench = load_json(args.bench)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"benchmark: no workload {args.workload!r} in {args.bench}",
              file=sys.stderr)
        return 2
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(cfg_entry["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    peaks = load_json("benchmark", "peaks.json")

    try:
        import jax

        import hadoop_bam_tpu  # noqa: F401 — the system under test
        from hadoop_bam_tpu.obs import trace as obs_trace
        from hadoop_bam_tpu.utils.metrics import base_metrics
    except ImportError as e:
        print(f"benchmark: the program is not importable here: {e}",
              file=sys.stderr)
        return 2
    runner = importlib.import_module(
        f"benchmark.runners.{traffic['runner']}")

    # persistent compile cache at a fixed path inside the checkout (the
    # one the program's own entry points use), or where the variable says
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: JAX found no device: {e}", file=sys.stderr)
        return 2
    platform = devices[0].platform
    cpu_asked = "cpu" in (jax.config.jax_platforms or "").lower().split(",")
    if platform != "tpu" and not (args.tiny and platform == "cpu"
                                  and cpu_asked):
        print(f"benchmark: JAX found no TPU (platform {platform!r}); "
              f"refusing to run.  The CPU is accepted only with --tiny "
              f"under an explicit JAX_PLATFORMS=cpu.", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 2
    if platform == "tpu" and devices[0].device_kind not in peaks["devices"]:
        print(f"benchmark: device kind {devices[0].device_kind!r} is not "
              f"in benchmark/peaks.json", file=sys.stderr)
        return 2

    watch = CompileWatch(jax)
    workdir = tempfile.mkdtemp(prefix="hbam_bench_")
    ctx = Context(args, cell, config, traffic, workdir)
    ctx.say(f"platform {platform} kind {devices[0].device_kind} devices "
            f"{len(devices)} seed {args.seed} seconds {args.seconds} trace "
            f"{args.trace} compile cache {cache_dir}")
    trace_dir = os.path.join(workdir, "trace")
    try:
        ctx.part_done("import")
        runner.setup(ctx)
        n_c, c_s, hits, misses = watch.state()
        setup_s = time.perf_counter() - T_START
        ctx.say("set-up %.3f s by part: %s; %d programs compiled or loaded "
                "in %.2f s (cache hits %d, misses %d)"
                % (setup_s, json.dumps({k: round(v, 3)
                                        for k, v in ctx.parts.items()}),
                   n_c, c_s, hits, misses))

        if args.sweep:
            runner.sweep(ctx, [float(r) for r in args.sweep.split(",")],
                         ctx.seconds)
            return 0

        recorder = None
        if ctx.traced:
            recorder = obs_trace.enable_tracing(capacity=1 << 20)
            opts = jax.profiler.ProfileOptions()
            # device ops and the program's own annotations, not every
            # Python call and runtime task (millions of events a window)
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        base_metrics().reset()
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("benchmark.window"):
                res = runner.measure(ctx)
        finally:
            t1 = time.perf_counter()
            snap = base_metrics().snapshot()
            if ctx.traced:
                jax.profiler.stop_trace()
                obs_trace.disable_tracing()
        n_c1, _s, hits1, misses1 = watch.state()
        # with the persistent cache in use every compile request is a hit
        # (a program loaded) or a miss (a program compiled)
        cached = hits1 + misses1 > 0
        compiled_in_window = misses1 - misses if cached else n_c1 - n_c
        loaded_in_window = hits1 - hits if cached else 0
        verify = getattr(runner, "verify", None)
        if verify is not None:          # checks too long for the window
            res["correct"] = bool(res["correct"]) and verify(ctx)
        ctx.say(f"window {t1 - t0:.3f} s; compilations inside the window: "
                f"{compiled_in_window}; programs re-traced and loaded from "
                f"the persistent cache inside it: {loaded_in_window}")
        correct = bool(res["correct"]) and compiled_in_window == 0

        doc = {"correct": correct, "attempted": int(res["attempted"]),
               "failed": int(res["failed"])}
        device = device_doc(devices)
        if ctx.traced:
            from benchmark import trace_reduce
            trace = trace_reduce.reduce(trace_dir, platform)
            ctx.say("trace inventory (plane, line, events): "
                    + json.dumps(trace.pop("inventory")))
            obs = dict(res.get("observations", {}))
            ctx.say("wall timers in the window (s): " + json.dumps(
                {k: round(v, 4) for k, v in snap["wall_timers"].items()}))
            ctx.say("counters in the window: "
                    + json.dumps(snap["counters"]))
            obs.update(snapshot=snap, window_s=t1 - t0, trace=trace,
                       span_durations=span_durations(recorder))
            doc["metrics"] = layer_metrics(bench, cell, obs, ctx.say)
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            if "breakdown" in trace:
                doc["breakdown"] = trace["breakdown"]
        else:
            doc["metrics"] = {
                m["name"]: {"value": res["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in bench["end_to_end"]
                if m["name"] != "setup_s"
                and ("workloads" not in m or cell["name"] in m["workloads"])}
            doc["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
        doc["device"] = device
    finally:
        teardown = getattr(runner, "teardown", None)
        if teardown is not None:
            teardown(ctx)
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
