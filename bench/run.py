#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with a TPU.  Set-up imports
JAX, refuses any platform but the TPU, builds the cell's first scenario
and warms the stage shapes the traffic uses from the compile cache
(``<checkout>/.jax_cache``).  The window then runs scenarios back to
back for ``--seconds`` of wall time (``bench/window.py``).  Afterwards
the check holds the stage windows to a plain recount and the
acknowledged records to their delivery (``bench/check.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
the engine profiler and the JAX profiler through the window and prints
the per-layer metrics, the device's busy time and a breakdown.  The
last line of stdout is one JSON object; the numbers compared for
``correct`` are the last lines of stderr and the last key of that
object.  With no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

SYNC = "bench.sync"


class Refused(Exception):
    """The run cannot be made here; nothing is printed on stdout."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices(chips: int):
    """JAX with its compile cache inside the checkout, at a fixed path,
    and the chips the cell asks for; refuses anything but a TPU."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # the stage programs are small: cache them whatever their compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r}); "
                      "the benchmark runs on the chip only")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return jax, devs


class Compiles:
    """JAX's own counts of traces, compiles and compile-cache hits."""

    # backend_compile_duration is recorded for every compile request,
    # a persistent-cache hit included
    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self, jax):
        self.n = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, event, **_):
        k = self.EVENTS.get(event)
        if k:
            self.n[k] += 1

    def _dur(self, event, duration, **_):
        self._event(event)

    def snap(self) -> dict:
        return dict(self.n)


def warm(scenario, buckets) -> int:
    """Run every stage shape the traffic uses once (set-up), through
    each stage module's ``warm``.  A cell whose traffic lists shapes
    that no stage warms, or whose stages have no shapes listed, is
    refused: its compiles would fall into the window."""
    eng = scenario.eng
    stages = scenario.capture.stages
    if bool(stages) != bool(buckets):
        raise Refused("the traffic's stage_buckets "
                      f"{list(buckets)} do not match the cell's "
                      f"{len(stages)} stage(s)")
    n = 0
    for rt, mod, _ in stages:
        try:
            n += mod.warm(rt, eng, buckets)
        except ValueError as e:
            raise Refused(str(e))
    return n


def run(argv=None) -> tuple[dict, list]:
    """One run; returns (result, compared rows).  Raises :class:`Refused`."""
    args = parse(argv)
    import numpy as np

    from bench import catalog, check, hostprof, window

    bench = catalog.load()
    try:
        cell = catalog.cell(bench, args.workload)
    except KeyError as e:
        raise Refused(str(e))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused("the program (src/repro) is not in this checkout")
    chips = int(cell["workload"]["chips"])
    marks = {"imports": time.perf_counter()}
    jax, devs = devices(chips)
    marks["devices"] = time.perf_counter()
    from repro import chip
    chip.init_jax()
    comp = Compiles(jax)
    trace = bool(args.trace)
    from jax import profiler

    def make(i):
        rec = hostprof.Recorder() if trace else None
        return window.Scenario(cell, args.seed, i, recorder=rec)

    try:
        first = make(0)
    except LookupError as e:
        raise Refused(str(e))
    marks["first_scenario"] = time.perf_counter()
    n_warm = warm(first, cell["traffic"].get("stage_buckets", []))
    marks["warm"] = time.perf_counter()
    tdir = sync = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(tdir, profiler_options=opts)
        sync = time.perf_counter_ns()
        with profiler.TraceAnnotation(SYNC):
            pass
    setup_s = time.perf_counter() - T_START
    t, phases = T_START, {}
    for k, v in marks.items():
        phases[f"setup_{k}_s"] = v - t
        t = v
    c0 = comp.snap()
    closed = {}

    def on_close():
        closed["compiles"] = comp.snap()
        if trace:
            profiler.stop_trace()

    scen, clock = window.drive(args.seconds, first, make, on_close)
    wall_s = clock.t_close - clock.t0
    sim_s = sum(s.window_sim_s for s in scen)
    c1 = closed.get("compiles") or comp.snap()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", -1)
               for d in devs[:chips])
    peak = None if peak < 0 else int(peak)

    # -- gather what the check and the readers need, then free the engines
    stage_windows, acked, delivered, groups = [], [], [], []
    skipped = 0
    for s in scen:
        cap = s.capture
        got, k = cap.stage_windows(s.end, s.grace)
        stage_windows += got
        skipped += k
        acked.append(s.acked_due())
        delivered.append(cap.delivered_ids())
        groups.append(cap.groups)
    walls = [w for s in scen for w in s.stage_walls()]
    ticks = sum(s.ticks for s in scen)
    host = {}
    events = host_sim = 0.0
    if trace:
        host = hostprof.merge(s.recorder for s in scen)
        events = sum(s.eng.n_events for s in scen) - ticks
        host_sim = sum(s.end for s in scen)
        spans = _spans(s.recorder for s in scen)
        if clock.builds:     # the harness's own: spec build + engine init
            b = np.array(clock.builds)
            spans["scenario_build"] = (b[:, 0], b[:, 1])
    n_scen = len(scen)
    del scen, first

    # -- the check: plain reference after the window
    st = check.check_stage(stage_windows)
    dv = {"undelivered": 0, "deliveries_checked": 0}
    for a, d, g in zip(acked, delivered, groups):
        r = check.check_delivery(a, d, g)
        dv = {k: dv[k] + r[k] for k in dv}
    ok, rows = check.verdict(st, dv)

    traced = None
    breakdown = None
    if trace:
        from bench import trace as tr
        got = tr.read(tdir, annotations=(SYNC,))
        shutil.rmtree(tdir, ignore_errors=True)
        traced, breakdown = tr.reduce(got, sync, clock.t_close, spans,
                                      chips, SYNC)

    stage_q = (statistics.quantiles(walls, n=10) if len(walls) >= 2
               else [float("nan")] * 9)
    runinfo = {
        "scenarios": n_scen, "window_sim_s": sim_s, "window_wall_s": wall_s,
        "stage_windows_in_window": len(walls),
        "stage_wall_p50_ms": 1e3 * stage_q[4],
        "stage_wall_p90_ms": 1e3 * stage_q[8],
        "stage_windows_skipped": skipped, "ticks": ticks,
        "warmed_shapes": n_warm, **phases,
        # a compile request that the persistent cache served is no compile
        "window_compiles": (c1["compiles"] - c0["compiles"])
        - (c1["cache_hits"] - c0["cache_hits"]),
        "window_cache_loads": c1["cache_hits"] - c0["cache_hits"],
        "window_traces": c1["traces"] - c0["traces"],
        "stage_traces_per_later_scenario":
            (c1["traces"] - c0["traces"]) / max(1, n_scen - 1),
    }
    data = {"sim_s": sim_s, "wall_s": wall_s, "setup_s": setup_s,
            "stage_walls": walls, "host": host, "host_sim_s": host_sim,
            "events": events, "trace": traced}
    metrics = {}
    for m in catalog.metrics_for(bench, args.workload, trace):
        v = catalog.reader(m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
    result = {"correct": ok,
              "attempted": st["stage_windows"] + dv["deliveries_checked"],
              "failed": st["stage_windows_bad"] + dv["undelivered"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["runinfo"] = runinfo
    result["compared"] = {name: {"value": v, "limit": lim,
                                 "kind": "at most" if k == "max"
                                 else "at least"}
                          for name, v, lim, k in rows}
    return result, rows


def _spans(recorders) -> dict:
    """Every recorder's host spans, by bucket, as numpy arrays (s)."""
    import numpy as np
    parts: dict = {}
    for r in recorders:
        for k, (a, b) in r.spans.items():
            parts.setdefault(k, []).append((np.frombuffer(a), np.frombuffer(b)))
    return {k: (np.concatenate([a for a, _ in v]),
                np.concatenate([b for _, b in v])) for k, v in parts.items()}


def main(argv=None) -> int:
    try:
        result, rows = run(argv)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    ri = result["runinfo"]
    print("bench run: " + ", ".join(f"{k} {v}" for k, v in ri.items()),
          file=sys.stderr)
    for name, v, lim, kind in rows:
        rel = "<=" if kind == "max" else ">="
        print(f"compared {name}: {v} (limit {rel} {lim})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
