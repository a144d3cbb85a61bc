"""From a profiler trace to device busy time, idle gaps and top ops.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation that ran (``XLA Modules``, one per program, where a plane has
no op line), with a start and a duration in nanoseconds on the trace's
clock.  Host annotations (``jax.profiler.TraceAnnotation``) sit
on the host plane's lines on the same clock.

Busy time is the union of the operation intervals, so overlapping ops
count once; the idle share of a window is one minus busy over the
window.  All functions below but :func:`read` take plain data, so the
arithmetic is tested without a trace.
"""
from __future__ import annotations

import glob
import os

OPS_LINES = ("XLA Ops", "XLA Modules")   # first one a plane has
STAGE_BUCKET = "operator"      # the engine profiler's span of a stage call


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e < s:
            raise ValueError(f"interval ends before it starts: {(s, e)}")
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def top_by_name(events, k: int = 10) -> list[list]:
    """``[[name, total seconds], ...]`` of the ``k`` names that took
    longest; ``events`` holds ``(name, start_ns, end_ns)``."""
    tot: dict[str, float] = {}
    for name, s, e in events:
        tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def overlap(starts, ends, lo: float, hi: float) -> float:
    """Time inside ``[lo, hi]`` covered by disjoint spans (arrays)."""
    import numpy as np
    st, en = np.asarray(starts, float), np.asarray(ends, float)
    m = (st < hi) & (en > lo)
    return float(np.sum(np.minimum(en[m], hi) - np.maximum(st[m], lo)))


def label_gaps(gap_list, host_spans: dict, k: int = 10,
               min_cover: float = 0.5) -> list[list]:
    """The ``k`` longest gaps, each named by the host bucket that covers
    most of it.  ``host_spans`` maps a bucket to ``(starts, ends)`` on
    the trace's clock; one bucket's spans never overlap each other.  A
    gap that no bucket covers for ``min_cover`` of its length is named
    ``unattributed``.  Returns ``[[name, seconds]]``."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:k]:
        best, cover = "unattributed", 0.0
        for name, (st, en) in host_spans.items():
            c = overlap(st, en, s, e)
            if c > cover:
                best, cover = name, c
        if cover < min_cover * (e - s):
            best = "unattributed"
        out.append([best, (e - s) * 1e-9])
    return out


def read(log_dir: str, annotations=()) -> dict:
    """Device ops per device plane, and host annotation intervals.

    Returns ``{"devices": {plane: [(name, start_ns, end_ns)]},
    "host": {annotation: [(start_ns, end_ns)]}}``.
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: dict[str, list] = {}
    host: dict[str, list] = {a: [] for a in annotations}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            use = next((n for n in OPS_LINES if n in lines), None)
            if use is None:
                continue
            devices[plane.name] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in lines[use].events]
        elif plane.name.startswith("/host:") and host:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        host[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {"devices": devices, "host": host}


def reduce(got: dict, sync_perf_ns: int, close_perf_s: float,
           host_spans_s: dict, chips: int, sync: str) -> tuple[dict, dict]:
    """The traced window's device numbers and its breakdown.

    ``got`` is :func:`read`'s output.  The window runs from the ``sync``
    annotation, made at ``sync_perf_ns`` on the host's
    ``perf_counter_ns``, to ``close_perf_s`` (``perf_counter``); that
    one mark maps the host's spans (``host_spans_s``: bucket ->
    ``(starts, ends)`` in perf_counter seconds) onto the trace's clock.
    Busy time is averaged over the first ``chips`` device planes.  The
    stage calls are the host spans of :data:`STAGE_BUCKET` that start
    inside the window.
    """
    if not got["host"].get(sync):
        raise RuntimeError("the trace lost its clock mark")
    t0 = got["host"][sync][0][0]
    off = t0 - sync_perf_ns                      # trace ns - perf ns
    t1 = t0 + (close_perf_s * 1e9 - sync_perf_ns)
    planes = sorted(got["devices"])[:chips]
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    busy_s, ops, every = [], [], []
    for p in planes:
        ev = got["devices"][p]
        iv = [(s, e) for _, s, e in ev]
        every += iv
        busy_s.append(busy(iv, t0, t1) * 1e-9)
        ops += [x for x in ev if x[1] < t1 and x[2] > t0]
    host_ns = {k: (a * 1e9 + off, b * 1e9 + off)
               for k, (a, b) in host_spans_s.items()}
    starts = host_ns.get(STAGE_BUCKET, ((), ()))[0]
    calls = sum(1 for s in starts if t0 <= s < t1)
    # the stage is the only program the window runs on the device, so
    # its device time is all of the window's busy time
    traced = {"busy_s": sum(busy_s) / len(busy_s),
              "window_s": (t1 - t0) * 1e-9,
              "stage_busy_s": sum(busy_s) / len(busy_s),
              "stage_calls": calls}
    breakdown = {"device_ops": top_by_name(ops),
                 "idle_gaps": label_gaps(gaps(every, t0, t1), host_ns)}
    return traced, breakdown
