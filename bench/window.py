"""The measured window: scenarios of one cell, back to back.

A run builds scenario 0 in set-up.  The window then runs it with
``Engine.run``, builds the next one (spec and engine, as a sweep user
pays for them) and runs that, until ``seconds`` of wall time have
passed.  A periodic tick in emulated time, scheduled by the harness and
counted apart, reads the wall clock; the first tick past the deadline
closes the window: the emulated time reached so far is what the window
completed.  The scenario that was cut runs on for the traffic's
``grace_s`` emulated seconds (or to its end), outside the window, so
that records acknowledged before the close have their time to arrive.

Capture, for the check, at the program's public boundaries only: each
record's payload as it is produced (``Monitor.produced``), each batch a
subscriber receives with its emulated time (``on_records`` and
``on_records_cohort``), and after the run each stream processor's
answers (``SPERuntime.outputs``).  The reference windows are cut from
the deliveries by the window length the deployment states, in emulated
time, and not by when the program ran its stage.
"""
from __future__ import annotations

import hashlib
import math
import time
from typing import Callable, Optional

import numpy as np

from bench import catalog

TICK_S = 0.05        # emulated seconds between two looks at the clock


def scenario_seed(seed: int, i: int) -> int:
    """Engine seed of scenario ``i`` of a run started with ``seed``."""
    h = hashlib.sha256(f"{int(seed)}:{int(i)}".encode()).digest()
    return int.from_bytes(h[:8], "little") >> 2


class Capture:
    """Deliveries, produced payloads and stage answers of one scenario."""

    def __init__(self, eng):
        from repro.core.spe import SPERuntime

        self.eng = eng
        self.groups: dict[str, list[str]] = {}   # topic -> group keys
        self.received: dict[str, list] = {}      # group -> [(t, ids)]
        self.stages: list[tuple] = []            # (runtime, module, group)
        for rt in eng.runtimes:
            topics = getattr(rt, "topics", None)
            if not topics or not hasattr(rt, "on_records"):
                continue
            group = getattr(rt, "group", None) or rt.name
            sink = self.received.setdefault(group, [])
            for t in topics:
                keys = self.groups.setdefault(t, [])
                if group not in keys:
                    keys.append(group)
            _hook(rt, sink)
            if isinstance(rt, SPERuntime):
                mod = catalog.stage(rt.query_name)
                if mod is None:
                    raise LookupError(
                        f"no bench/stages/{rt.query_name}.py: the benchmark "
                        f"can neither warm nor check stage {rt.query_name!r}")
                self.stages.append((rt, mod, group))
        keep = {t for rt, _, _ in self.stages for t in rt.topics}
        self.payloads: dict[int, object] = {}
        if keep:
            self._hook_produced(eng.monitor, keep)

    def _hook_produced(self, mon, topics) -> None:
        orig = mon.produced
        payloads = self.payloads

        def produced(rec):
            if rec.topic in topics:
                payloads[rec.msg_id] = rec.payload
            return orig(rec)

        mon.produced = produced

    # -- after the run ---------------------------------------------------

    def delivered_ids(self) -> dict[str, np.ndarray]:
        return {g: _cat([ids for _, ids in got])
                for g, got in self.received.items()}

    def stage_windows(self, end: float, grace: float) -> tuple[list, int]:
        """``[(module, component, payloads or None, answer or None)]``,
        one per window the check compares, and the number of windows
        left out because the stream processor's host went down.

        Window ``k`` of a stage with window length ``w`` holds what the
        processor received in ``((k-1) w, k w]``; it closes at ``k w``
        and answers once where the stage's recount finds something.
        Answers pair with those windows in order.  A window that closed
        ``grace`` emulated seconds before the scenario's ``end`` has to
        have its answer; an answer with no window is compared against
        nothing and fails."""
        out, skipped = [], 0
        downs = {e.get("host") for e in self.eng.monitor.events
                 if e["kind"] == "host_down"}
        for rt, mod, group in self.stages:
            w = float(rt.comp.get("window"))
            svc = mod.services(rt.comp)
            per: dict[int, list] = {}
            for t, ids in self.received[group]:
                per.setdefault(max(1, math.ceil(t / w)), []).append(ids)
            wins = []
            for k in sorted(per):
                if k * w > end:
                    break                  # never closed in this run
                pay = [self.payloads[i] for i in _cat(per[k]).tolist()]
                if mod.recount(pay, svc) is not None:
                    wins.append((k, pay))
            if rt.host in downs:           # its buffer died with the host
                skipped += len(wins)
                continue
            answers = list(rt.outputs)
            for i, (k, pay) in enumerate(wins):
                if i < len(answers):
                    out.append((mod, rt.comp, pay, answers[i]))
                elif k * w <= end - grace:
                    out.append((mod, rt.comp, pay, None))
            for a in answers[len(wins):]:
                out.append((mod, rt.comp, None, a))
        return out, skipped


def _hook(rt, sink: list) -> None:
    """Record every batch the subscriber takes in, with its emulated time.

    A cohort arrives through ``on_records_cohort``, which may hand each
    view on to ``on_records``: those inner calls are not counted twice.
    A batch that lands while the subscriber is down dies there and is
    not counted."""
    one, many = rt.on_records, rt.on_records_cohort
    inside = [False]

    def on_records(eng, records):
        if not inside[0] and getattr(rt, "alive", True):
            sink.append((eng.now, _ids(records)))
        return one(eng, records)

    def on_records_cohort(eng, batches):
        if getattr(rt, "alive", True):
            now = eng.now
            for b in batches:
                sink.append((now, _ids(b)))
        inside[0] = True
        try:
            return many(eng, batches)
        finally:
            inside[0] = False

    rt.on_records = on_records
    rt.on_records_cohort = on_records_cohort


def _ids(batch) -> np.ndarray:
    ids = getattr(batch, "msg_id", None)
    if isinstance(ids, np.ndarray):
        return ids.copy()
    return np.array([r.msg_id for r in batch], np.int64)


def _cat(parts) -> np.ndarray:
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


class Scenario:
    """One engine of the cell, with its capture and tick."""

    def __init__(self, cell: dict, seed: int, i: int, *, recorder=None):
        from repro.core import Engine

        self.i = i
        self.horizon = float(cell["traffic"]["scenario_s"])
        self.grace = float(cell["traffic"]["grace_s"])
        spec = catalog.kind(cell["config"]["kind"]).build(
            catalog.params(cell), cell["traffic"])
        self.eng = Engine(spec, seed=scenario_seed(seed, i))
        if recorder is not None:
            self.eng.profiler = self.eng.net.profiler = recorder
        self.recorder = recorder
        self.capture = Capture(self.eng)
        self.ticks = 0
        self.sim_close: Optional[float] = None   # set when cut
        self.end = self.horizon                  # emulated time reached

    def run(self, clock: "Clock") -> None:
        eng = self.eng

        def tick():
            self.ticks += 1
            if clock.expired():
                self.sim_close = eng.now
                clock.close()
                stop_at = eng.now + self.grace
                if stop_at < self.horizon:
                    self.end = stop_at
                    eng.schedule_at(stop_at, eng.stop)
                    self.ticks += 1
                return
            eng.schedule(TICK_S, tick)

        eng.schedule(TICK_S, tick)
        eng.run(until=self.horizon)

    @property
    def window_sim_s(self) -> float:
        return self.horizon if self.sim_close is None else self.sim_close

    def due_cut(self) -> float:
        """Records acknowledged up to here had their time to arrive."""
        if self.sim_close is not None:
            return min(self.sim_close, self.end - self.grace)
        return self.end - self.grace

    def acked_due(self) -> dict[str, np.ndarray]:
        cut = self.due_cut()
        out: dict[str, list] = {}
        for m in self.eng.monitor.msgs.values():
            t = m.ack_time
            if t is not None and t <= cut and m.truncated_time is None:
                out.setdefault(m.topic, []).append(m.msg_id)
        return {k: np.array(v, np.int64) for k, v in out.items()}

    def stage_walls(self) -> list[float]:
        """The program's measured stage wall of every window inside the
        measured window (its ``spe_exec`` events)."""
        lim = self.window_sim_s
        return [e["wall"] for e in self.eng.monitor.events
                if e["kind"] == "spe_exec" and e["t"] <= lim]


class Clock:
    """Wall-clock deadline of the window, read by the scenarios' ticks."""

    def __init__(self, seconds: float, on_close=None):
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds
        self.t_close: Optional[float] = None
        self.on_close = on_close

    def expired(self) -> bool:
        return time.perf_counter() >= self.deadline

    def close(self) -> None:
        self.t_close = time.perf_counter()
        if self.on_close is not None:
            self.on_close()

    @property
    def closed(self) -> bool:
        return self.t_close is not None


def drive(seconds: float, first: Scenario,
          make: Callable[[int], Scenario], on_close=None
          ) -> tuple[list[Scenario], Clock]:
    """Run scenarios from ``first`` on until the window closes."""
    clock = Clock(seconds, on_close)
    clock.builds = []                # (start, end) of each later build
    scen = [first]
    while True:
        scen[-1].run(clock)
        if clock.closed:
            break
        if clock.expired():          # closed between two scenarios
            clock.close()
            break
        t = time.perf_counter()
        nxt = make(len(scen))
        clock.builds.append((t, time.perf_counter()))
        scen.append(nxt)
        if clock.expired():          # the build itself ran past it
            nxt.sim_close = nxt.end = 0.0
            clock.close()
            break
    return scen, clock
