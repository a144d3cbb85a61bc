"""Host-side layer times from the engine profiler's own hooks.

The program's :class:`repro.core.telemetry.Profiler` is fed at the layer
boundaries (``netem_path``, ``fetch_ctl``, ``deliver_cohort``,
``operator``, ...) with ``add(phase, dt)`` / ``add_wall(phase, dt)``,
called as each span ends.  Its buckets nest: a fetch runs path queries,
a delivery cohort may run a fetch.  :class:`Recorder` stands in for the
profiler object and turns those calls into *self* time online: a span
that ends at ``t`` after ``dt`` seconds covers every earlier span that
started at or after ``t - dt``, so those are popped off a stack as its
children and their time is taken out of its own.  ``scheduler_pop`` and
``event_fn`` arrive once per run as totals; they are kept apart.

Each span's interval is kept too (``spans``), so that idle gaps in a
device trace can be put beside what the host was doing at the time.
"""
from __future__ import annotations

import time
from array import array

TOTALS = ("scheduler_pop", "event_fn")


class Recorder:
    """Drop-in for the engine's ``Profiler`` that also keeps self time."""

    __slots__ = ("counts", "wall", "self_s", "spans", "_stack", "_clock")

    def __init__(self, clock=time.perf_counter):
        self.counts: dict[str, int] = {}
        self.wall: dict[str, float] = {}       # as the program sums them
        self.self_s: dict[str, float] = {}     # children taken out
        self.spans: dict[str, tuple[array, array]] = {}
        self._stack: list[tuple[float, float]] = []   # (start, dur)
        self._clock = clock

    # -- the Profiler surface the program calls ------------------------

    def add(self, phase: str, dt: float, n: int = 1) -> None:
        self.counts[phase] = self.counts.get(phase, 0) + n
        self.add_wall(phase, dt)

    def add_wall(self, phase: str, dt: float) -> None:
        self.wall[phase] = self.wall.get(phase, 0.0) + dt
        if phase in TOTALS or dt <= 0.0:
            return
        end = self._clock()
        self._close(phase, end - dt, dt)

    # -- bookkeeping -----------------------------------------------------

    def _close(self, phase: str, start: float, dt: float) -> None:
        st = self._stack
        child = 0.0
        while st and st[-1][0] >= start:
            child += st.pop()[1]
        self.self_s[phase] = self.self_s.get(phase, 0.0) + (dt - child)
        st.append((start, dt))
        if len(st) > 1 << 16:     # old top-level spans never nest again
            del st[:1 << 15]
        sp = self.spans.get(phase)
        if sp is None:
            sp = self.spans[phase] = (array("d"), array("d"))
        sp[0].append(start)
        sp[1].append(start + dt)


def merge(recs) -> dict:
    """Sum several recorders' self and total seconds by bucket."""
    out: dict[str, float] = {}
    for r in recs:
        for k, v in r.self_s.items():
            out[k] = out.get(k, 0.0) + v
        for k in TOTALS:
            if k in r.wall:
                out[k] = out.get(k, 0.0) + r.wall[k]
    return out


def layer_ms_per_sim_s(self_s: dict, sim_s: float) -> dict:
    """Wall ms per emulated second for every bucket, plus
    ``unattributed``: the part of ``event_fn`` in no named bucket.

    The buckets then add up to ``scheduler_pop + event_fn``, the
    profiled loop's whole wall time."""
    if sim_s <= 0:
        return {}
    named = sum(v for k, v in self_s.items() if k not in TOTALS)
    out = {k: 1e3 * v / sim_s for k, v in self_s.items()}
    if "event_fn" in self_s:
        out["unattributed"] = 1e3 * (self_s["event_fn"] - named) / sim_s
    return out
