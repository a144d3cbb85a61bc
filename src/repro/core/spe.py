"""Stream processing engine runtime: operator graphs over keyed state.

Since the event-time refactor the SPE is an **operator chain**
(:mod:`repro.core.operators`): each runtime drives a composable list of
stateful operators (``Map`` / ``Filter`` / ``KeyBy`` / ``TumblingWindow``
/ ``SlidingWindow`` / ``WindowAggregate`` / ``Sink``) instead of one
monolithic query function.  Two time modes (``timeMode`` cfg):

``processing`` (default, the legacy surface)
    Batches are pushed through the chain as they arrive; ``window > 0``
    keeps the old processing-time buffer + flush timer.  This path is
    scheduled exactly like the pre-refactor runtime and reproduces its
    event streams bit-for-bit (pinned in ``tests/test_metrics_pin.py``).

``event``
    Producers stamp ``event_time`` into records
    (:mod:`repro.core.stubs`), brokers carry the column per partition
    (:class:`~repro.core.broker.RecordBatch`), and the runtime derives
    its **watermark** as the min over its owned partitions' running-max
    event times.  Window panes fire when the watermark passes
    ``window_end + allowed_lateness``.

    *Determinism*: a record is **late** iff its event time trails its
    *own partition's* running max by more than ``allowedLateness`` — a
    per-partition criterion, deterministic regardless of how partitions
    interleave across the ``poll``/``wakeup`` delivery modes.  Sketch of
    why no on-time record can miss its window: the window fires only
    once *every* owned partition's max event time passed
    ``end + lateness``; a record from partition p arriving later
    therefore trails p's max by more than ``lateness`` whenever its
    event time is inside the window — i.e. it is late by the
    per-partition rule.  Window contents are thus a pure function of the
    per-partition record sequences, and firing order is sorted
    ``(window_start, key)`` — identical across delivery modes and
    processes (the sweep fingerprint contract).

    *Idle partitions*: an owned partition that never receives a record
    pins the watermark at -inf and no window ever fires — the classic
    idle-partition stall, surfaced deterministically instead of papered
    over with wall-clock idleness timeouts (which would break the
    determinism contract).  Feed every partition (unkeyed round-robin
    does) or shrink the partition count / key space accordingly.

**Checkpointed recovery** (``checkpointInterval > 0``): every interval
the runtime snapshots operator state + per-partition processed offsets
to a :class:`~repro.core.state.StateBackend` (durable, outside the
emulated host).  A ``host_down`` fault wipes the volatile state; on
recovery the controller restores the latest snapshot and seeks the
committed input offsets back to the checkpointed positions, replaying
the suffix.  ``semantics`` selects the emission contract:

- ``at_least_once`` — results are produced when windows fire; windows
  that fired after the last checkpoint fire *again* on replay
  (``recovered_duplicates`` in :meth:`Engine.metrics` counts them);
- ``exactly_once`` — results are buffered and only produced at the next
  checkpoint (a transactional sink); uncommitted outputs die with the
  host and are regenerated by replay, so the output topic sees every
  window exactly once.

Legacy queries (Table II applications + §V-C reproductions + LM jobs)
are reimplemented as operator chains behind the :class:`Query` compat
shim — per-record queries are ``Map``/``StatefulMap`` stages, whole-
batch queries a single ``BatchOp`` stage — with the original
``query(spe, eng, records)`` call signature preserved:

  split, count, avg_len_by_topic            — word count pipeline
  sentiment                                 — unstructured data
  ride_select                               — join/groupby/window, stateful
  maritime                                  — windowed counts → ext. store
  fraud_svm                                 — ML prediction (linear SVM)
  traffic_metrics                           — Ocampo traffic monitoring
  lm_generate                               — serve an LM over the stream
  identity                                  — passthrough
"""
from __future__ import annotations

import collections
import copy
import sys
import time
from typing import Any, Callable, Optional

import numpy as np

from repro import chip
from repro.core import state as state_mod
from repro.core.broker import BatchView
from repro.core.operators import (
    BatchOp, ColumnBatch, Element, KeyBy, Map, OpContext, OperatorChain,
    SlidingWindow, StatefulMap, TumblingWindow, WindowAggregate,
    jit_bucket,
)
from repro.core.spec import Component
from repro.core.stubs import PER_BYTE_S, PER_RECORD_S
from repro.core.subscription import DeliveryLoop

__all__ = ["SPERuntime", "make_spe", "Query", "QUERIES", "jit_bucket",
           "WINDOW_BASE_S", "build_event_chain", "elements_of",
           "StageParts", "stage_parts"]

WINDOW_BASE_S = 200e-6


def elements_of(records) -> list[Element]:
    """Normalize a delivered batch (:class:`~repro.core.broker.BatchView`
    / ``Record`` list / ``Element`` list) to operator elements.

    The view branch reads the columns directly — no intermediate
    ``Record`` dataclasses — with python-scalar conversion done in one C
    pass (``tolist``), so payload-visible values (sizes, event times)
    stay plain ints/floats exactly as on the legacy path."""
    if isinstance(records, BatchView):
        return [Element(p, s, t, k)
                for p, s, t, k in zip(records.payloads, records.sizes(),
                                      records.event_times(), records.keys)]
    if records and isinstance(records[0], Element):
        return list(records)
    return [Element(r.payload, r.size, getattr(r, "event_time", 0.0),
                    getattr(r, "key", None)) for r in records]


def _batch_nbytes(records) -> int:
    if isinstance(records, BatchView):
        return records.total_bytes()
    return sum(r.size for r in records)


# ---------------------------------------------------------------------------
# Engine-profiler spans of a query call
# ---------------------------------------------------------------------------


def _annotation(jax, name: str):
    """An entered ``jax.profiler.TraceAnnotation``, or ``None`` where JAX
    is not loaded (then there is no device trace to sit in)."""
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class StageParts:
    """The parts of one jitted stage call, timed as children of the
    runtime's ``operator`` span.

    A query marks where each part begins (:meth:`begin`); a part ends
    where the next one begins, or at :meth:`end`, and is added to the
    engine profiler under its name:

    - ``stage_build``: decode and pad the inputs;
    - ``stage_compile``: the first call of a jitted function new to this
      query instance (trace, then compile or compile-cache load);
    - ``stage_dispatch``: every later call;
    - ``stage_wait``: ``block_until_ready``;
    - ``stage_readback``: one host copy of the stage's results, and
      the Python values built from it.

    Where JAX is loaded each part also runs under a
    ``jax.profiler.TraceAnnotation`` of the same name, so that it sits
    in a device trace on the trace's own clock.  The runtime hands one
    out (``SPERuntime.stage_parts``) only while its ``operator`` span is
    open; :func:`stage_parts` gives any other caller :data:`NO_PARTS`,
    which records nothing.
    """

    __slots__ = ("prof", "jax", "name", "ann", "t0")

    def __init__(self, prof, jax=None) -> None:
        self.prof = prof
        self.jax = jax
        self.name: Optional[str] = None
        self.ann = None
        self.t0 = 0.0

    def begin(self, name: str) -> None:
        if self.prof is None:
            return
        self.end()
        self.name = name
        self.ann = _annotation(self.jax, name)
        self.t0 = time.perf_counter()

    def end(self) -> None:
        if self.name is None:
            return
        self.prof.add(self.name, time.perf_counter() - self.t0)
        self._close()

    def drop(self) -> None:
        """Close the open part unrecorded: the call reached no device."""
        if self.name is not None:
            self._close()

    def _close(self) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        self.name = None


NO_PARTS = StageParts(None)


def stage_parts(spe) -> StageParts:
    """The stage parts of ``spe``'s open ``operator`` span, else
    :data:`NO_PARTS` (a direct ``query(...)`` call records nothing)."""
    return getattr(spe, "stage_parts", None) or NO_PARTS


class OperatorSpan:
    """The runtime's ``operator`` span around its query work, with the
    profiler on: the wall time goes to ``prof`` on exit, the query's
    :class:`StageParts` are open inside it, and where JAX is loaded the
    whole span sits under a ``TraceAnnotation("operator")``."""

    __slots__ = ("rt", "prof", "ann", "t0", "wall")

    def __init__(self, rt, prof) -> None:
        self.rt = rt
        self.prof = prof
        self.wall = 0.0

    def __enter__(self) -> "OperatorSpan":
        jax = sys.modules.get("jax")
        self.ann = _annotation(jax, "operator")
        self.rt.stage_parts = StageParts(self.prof, jax)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_) -> None:
        parts, self.rt.stage_parts = self.rt.stage_parts, None
        if exc_type is None:
            parts.end()
        else:
            parts.drop()
        self.wall = time.perf_counter() - self.t0
        self.prof.add("operator", self.wall)
        if self.ann is not None:
            self.ann.__exit__(None, None, None)


# ---------------------------------------------------------------------------
# SPE runtime
# ---------------------------------------------------------------------------


class SPERuntime(DeliveryLoop):
    stage_parts: Optional[StageParts] = None   # set inside ``operator``

    def __init__(self, comp: Component, host: str):
        self.init_subscriber(
            comp, host, [comp.get("inTopic") or comp.get("topic")])
        self.in_topic = self.topics[0]
        self.out_topic = comp.get("outTopic")
        self.query_name = comp.get("query", "identity")
        self.window_s = float(comp.get("window", 0.0))
        self.time_mode = comp.get("timeMode", "processing")
        self.allowed_lateness = float(comp.get("allowedLateness", 0.0))
        self.checkpoint_interval = float(comp.get("checkpointInterval", 0.0))
        self.semantics = comp.get("semantics", "at_least_once")
        self.query = QUERIES[self.query_name](comp)
        if self.time_mode == "event":
            self.chain = build_event_chain(comp, self.query)
        else:
            # the compat adapter (query.__call__) runs this same chain,
            # so checkpoints cover the processing-mode state too
            self.chain = self.query.chain
        self._query_init = self.query.attr_snapshot()
        self.buffer: list = []
        self.outputs: list = []            # retained for assertions
        self.n_processed = 0
        self.late_records = 0
        self.alive = True
        self._maxet: dict[int, float] = {}     # partition -> max event time
        self._proc_off: dict[int, int] = {}    # partition -> next offset
        self._pending_out: list[Element] = []  # exactly_once: uncommitted
        self._ckpt_epoch = 0
        self.backend: Optional[state_mod.StateBackend] = None
        self._ctx = OpContext(None, self)

    # consumer-side ---------------------------------------------------------

    def start(self, eng) -> None:
        state_dir = self.comp.get("stateDir")
        self.backend = (state_mod.make_backend(state_dir) if state_dir
                        else eng.state_backend)
        self._ctx = OpContext(eng, self)
        self.chain.open(self._ctx)
        self.start_delivery(eng, [self.in_topic])
        if self.window_s > 0 and self.time_mode != "event":
            eng.schedule(self.window_s, lambda: self.flush(eng))
        if self.checkpoint_interval > 0:
            eng.schedule(self.checkpoint_interval,
                         lambda: self._checkpoint_tick(eng))

    def on_records_cohort(self, eng, batches) -> None:
        """Cohort ingest: the liveness gate is hoisted — no event can
        pop between the views of one cohort event, so ``alive`` (and
        ``_bp_epoch``) cannot change mid-cohort and checking once is
        provably equivalent to the per-view check.  Each view is then
        ingested in landing order: per-partition offset bookkeeping,
        admission and watermark advancement are order-sensitive and
        stay per-view (ROADMAP cohort contract)."""
        if not self.alive:
            return                    # host is down: in-flight cohort dies
        for b in batches:
            self._ingest_one(eng, b)

    def on_records(self, eng, records) -> None:
        if not self.alive:
            return                    # host is down: in-flight batch dies
        self._ingest_one(eng, records)

    def _ingest_one(self, eng, records) -> None:
        if isinstance(records, BatchView):
            # one fetch response covers one partition's dense row range
            self._proc_off[records.partition] = records.hi
        else:
            for r in records:
                self._proc_off[r.partition] = r.offset + 1
        # offsets above already advanced: anything shed at admission is
        # consumed-but-dropped, never replayed (delivered rows untouched)
        records = self.bp_admit(eng, records)
        if self.queue_bytes_max > 0 and not len(records):
            return
        if self.time_mode == "event":
            self._ingest_event(eng, records)
        elif self.window_s > 0:
            # buffer lightweight Elements, never Records — the flush
            # path only needs (payload, size, event_time, key)
            self.buffer.extend(elements_of(records))
            if self.queue_bytes_max > 0:
                # buffered rows hand off to the window flush; the ingest
                # queue's job (bounding the fetch path) is done
                self.bp_drain(eng, _batch_nbytes(records), self._bp_epoch)
        else:
            self._process(eng, records)

    def flush(self, eng) -> None:
        batch, self.buffer = self.buffer, []
        if batch:
            self._process(eng, batch)
        eng.schedule(self.window_s, lambda: self.flush(eng))

    # processing-time path (legacy; bit-for-bit with the pre-refactor
    # runtime — pinned) ------------------------------------------------------

    def _process(self, eng, records) -> None:
        nbytes = _batch_nbytes(records)
        service = (WINDOW_BASE_S + PER_RECORD_S * len(records)
                   + PER_BYTE_S * nbytes)
        prof = eng.profiler
        if prof is None:
            t0 = time.perf_counter()
            results = self.query(self, eng, records)   # REAL compute, now
            wall = time.perf_counter() - t0
        else:
            with OperatorSpan(self, prof) as span:
                results = self.query(self, eng, records)
            wall = span.wall
        self.n_processed += len(records)
        if self.query.measure_wall:
            eng.monitor.event(eng.now, "spe_exec", spe=self.name,
                              wall=wall, records=len(records))

        ep = self._bp_epoch

        def _emit():
            if not self.alive:
                return
            for payload, size in results:
                self.outputs.append(payload)
                if self.out_topic:
                    eng.cluster.produce(self.host, self.name, self.out_topic,
                                        payload, size)
            if self.queue_bytes_max > 0:
                self.bp_drain(eng, nbytes, ep)

        done = eng.execute_on(self.host, service, _emit)
        tel = eng.telemetry
        if tel is not None:
            # operator span: queueing + service time of this batch on
            # the host compute model (sim time, deterministic)
            tel.span("operator", self.in_topic, done - eng.now)

    # event-time path (watermarks + window firing) ---------------------------

    def _watermark(self, eng) -> float:
        parts = eng.cluster.assigned_partitions(self, self.in_topic)
        if not parts:
            return float("-inf")
        return min(self._maxet.get(p, float("-inf")) for p in parts)

    def _ingest_event(self, eng, records) -> None:
        prof = eng.profiler
        if prof is None:
            outs = self._event_chain(eng, records)
        else:
            with OperatorSpan(self, prof):
                outs = self._event_chain(eng, records)
        nbytes = _batch_nbytes(records)
        service = (WINDOW_BASE_S + PER_RECORD_S * len(records)
                   + PER_BYTE_S * nbytes)
        ep = self._bp_epoch
        if self.semantics == "exactly_once" and self.checkpoint_interval > 0:
            # transactional sink: hold results until the checkpoint
            # commits them (uncommitted outputs die with the host)
            self._pending_out.extend(outs)
            if self.queue_bytes_max > 0:
                done = eng.execute_on(self.host, service,
                                      lambda: self.bp_drain(eng, nbytes, ep))
            else:
                # fn=None schedules no event — keeps unbounded runs
                # event-count-identical to the pre-backpressure build
                done = eng.execute_on(self.host, service)
        else:
            def _emit_and_drain():
                self._emit_elems(eng, outs)
                if self.queue_bytes_max > 0:
                    self.bp_drain(eng, nbytes, ep)
            done = eng.execute_on(self.host, service, _emit_and_drain)
        tel = eng.telemetry
        if tel is not None:
            tel.span("operator", self.in_topic, done - eng.now)

    def _event_chain(self, eng, records) -> list[Element]:
        """Lateness, the chain and the watermark: the ``operator`` work
        of one event-time batch."""
        if isinstance(records, BatchView):
            outs, n_late = self._ingest_view(records)
        else:
            elems: list[Element] = []
            n_late = 0
            for r in records:
                et = r.event_time
                prev = self._maxet.get(r.partition)
                if prev is not None and et + self.allowed_lateness < prev:
                    n_late += 1        # late by its own partition's clock
                    continue
                if prev is None or et > prev:
                    self._maxet[r.partition] = et
                elems.append(Element(r.payload, r.size, et, r.key))
            outs = self.chain.process(elems, self._ctx)
        if n_late:
            self.late_records += n_late
            eng.monitor.event(eng.now, "late_records", spe=self.name,
                              n=n_late)
        self.n_processed += len(records)
        outs += self.chain.advance_watermark(self._watermark(eng),
                                             self._ctx)
        return outs

    def _ingest_view(self, view: BatchView) -> tuple[list[Element], int]:
        """Columnar event-time ingest: per-partition lateness against the
        running max, then the chain's ``process_cols`` fast path.

        The sequential late/max scan is the same float comparisons, in
        the same order, as the per-record path — a view covers exactly
        one partition, so the running max is a plain local."""
        ets = view.event_times()
        part = view.partition
        lat = self.allowed_lateness
        prev = self._maxet.get(part, float("-inf"))
        keep: Optional[list[int]] = None      # None = nothing dropped yet
        n_late = 0
        for i, et in enumerate(ets):
            if et + lat < prev:
                n_late += 1
                if keep is None:
                    keep = list(range(i))
                continue
            if et > prev:
                prev = et
            if keep is not None:
                keep.append(i)
        if ets:
            self._maxet[part] = prev
        payloads, sizes, keys = view.payloads, view.sizes(), view.keys
        if keep is None:
            cols = ColumnBatch(payloads, sizes, ets, keys)
        else:
            cols = ColumnBatch([payloads[i] for i in keep],
                               [sizes[i] for i in keep],
                               [ets[i] for i in keep],
                               [keys[i] for i in keep])
        return self.chain.process_cols(cols, self._ctx), n_late

    def _emit_elems(self, eng, elems: list[Element]) -> None:
        if not self.alive:
            return
        for e in elems:
            self.outputs.append(e.payload)
            if e.window is not None:
                eng.monitor.event(eng.now, "window_emit", spe=self.name,
                                  key=e.window[0], start=e.window[1],
                                  end=e.window[2])
            if self.out_topic:
                eng.cluster.produce(
                    self.host, self.name, self.out_topic, e.payload,
                    e.size, key=e.key,
                    event_time=(e.event_time if self.time_mode == "event"
                                else None))

    # checkpointing + recovery ----------------------------------------------

    def _snapshot(self) -> dict:
        return {
            "epoch": self._ckpt_epoch,
            "chain": self.chain.snapshot(),
            "attrs": self.query.attr_snapshot(),
            "buffer": list(self.buffer),
            "maxet": dict(self._maxet),
            "proc_off": dict(self._proc_off),
        }

    def _checkpoint_tick(self, eng) -> None:
        if self.alive:
            if self._pending_out:
                pend, self._pending_out = self._pending_out, []
                self._emit_elems(eng, pend)    # commit held outputs first
            self._ckpt_epoch += 1
            prof = eng.profiler
            t0 = time.perf_counter() if prof is not None else 0.0
            self.backend.put(self.name, self._snapshot())
            if prof is not None:
                prof.add("checkpoint", time.perf_counter() - t0)
            eng.monitor.event(eng.now, "checkpoint", spe=self.name,
                              epoch=self._ckpt_epoch,
                              offsets=dict(sorted(self._proc_off.items())))
        eng.schedule(self.checkpoint_interval,
                     lambda: self._checkpoint_tick(eng))

    def on_host_down(self, eng) -> None:
        """Host failure: volatile operator state is lost."""
        self.alive = False
        self.chain.reset()
        self.query.attr_restore(self._query_init)
        self.buffer = []
        self._maxet = {}
        self._proc_off = {}
        self._pending_out = []
        # queued-but-unprocessed bytes die with the host; in-flight
        # drains for them are stale (epoch bump ignores them) and any
        # paused loop resumes into interval retries until recovery
        self.bp_reset(eng)

    def on_host_up(self, eng) -> None:
        """Recovery: restore the latest checkpoint and seek the committed
        input offsets back to the checkpointed positions (records past
        them replay).  Without checkpointing this is a cold restart —
        accumulated state and any unfired windows are simply lost."""
        self.alive = True
        snap = (self.backend.latest(self.name)
                if self.backend is not None and self.checkpoint_interval > 0
                else None)
        if snap is None:
            eng.monitor.event(eng.now, "spe_cold_restart", spe=self.name)
            return
        self.chain.restore(snap["chain"])
        self.query.attr_restore(snap["attrs"])
        self.buffer = list(snap["buffer"])
        self._maxet = dict(snap["maxet"])
        self._proc_off = dict(snap["proc_off"])
        self._ckpt_epoch = snap["epoch"]
        group = eng.cluster.group_of(self)
        for p in eng.cluster.assigned_partitions(self, self.in_topic):
            eng.cluster.seek(self.in_topic, p, group,
                             self._proc_off.get(p, 0))
        # the seek may have re-opened committed data behind a *parked*
        # waiter (this runtime drained the topic and parked before the
        # fault; the HW never advances again once producers finished) —
        # wake the topic's waiters so the rewound suffix actually
        # replays.  Runtimes holding a scheduled fetch instead are not
        # in the waiter set, so the one-of-{fetch event, waiter}
        # invariant is preserved.
        eng.cluster._notify(self.in_topic)
        eng.monitor.event(eng.now, "spe_recovered", spe=self.name,
                          epoch=self._ckpt_epoch)


def make_spe(comp: Component, host: str) -> SPERuntime:
    return SPERuntime(comp, host)


def build_event_chain(comp: Component, query: "Query") -> OperatorChain:
    """Event-time chain from cfg: ``chain`` (explicit operator list) or
    query stages + ``keyField``/``window``/``windowSlide``/``agg``."""
    explicit = comp.get("chain")
    if explicit is not None:
        return OperatorChain(list(explicit))
    ops = [] if isinstance(query, IdentityQuery) else query.ops()
    window = float(comp.get("window", 0.0))
    if window > 0:
        lateness = float(comp.get("allowedLateness", 0.0))
        slide = float(comp.get("windowSlide", 0.0))
        ops.append(KeyBy(comp.get("keyField")))
        ops.append(SlidingWindow(window, slide, lateness) if slide > 0
                   else TumblingWindow(window, lateness))
        ops.append(WindowAggregate(comp.get("agg", "count"),
                                   comp.get("valueField")))
    return OperatorChain(ops)


# ---------------------------------------------------------------------------
# Query compat shim: legacy queries as operator chains
# ---------------------------------------------------------------------------


def _unit_of(payload) -> Optional[Any]:
    return (payload.get("unit") if isinstance(payload, dict) else None)


def _data_of(payload) -> Any:
    return (payload["data"] if isinstance(payload, dict)
            and "data" in payload else payload)


def _wrapped(payload: Any, size: int, unit) -> tuple[Any, int]:
    if unit is not None:
        return {"unit": unit, "data": payload}, size
    return payload, size


class Query:
    """Compat shim over an operator chain.

    Subclasses describe themselves as operators (:meth:`ops`); the
    original ``query(spe, eng, records) -> [(payload, size)]`` call
    signature is preserved by running the chain over the batch.
    ``state_attrs`` names instance attributes that belong to the
    checkpointable state (operator-level state lives in the ops).
    """

    measure_wall = False
    state_attrs: tuple = ()

    def __init__(self, comp: Component):
        self.comp = comp
        self._chain: Optional[OperatorChain] = None

    # -- operator-chain surface -----------------------------------------

    def ops(self) -> list:
        """Default: one whole-batch compat stage around :meth:`_run`."""
        return [BatchOp(self._run_elems)]

    @property
    def chain(self) -> OperatorChain:
        if self._chain is None:
            self._chain = OperatorChain(self.ops())
        return self._chain

    def _run_elems(self, elems, ctx) -> list[tuple[Any, int]]:
        return self._run(ctx.runtime, ctx.eng, elems)

    def _run(self, spe, eng, records) -> list[tuple[Any, int]]:
        raise NotImplementedError

    def __call__(self, spe, eng, records) -> list[tuple[Any, int]]:
        ctx = OpContext(eng, spe)
        if isinstance(records, BatchView):
            # columnar ingest: chain fast paths run without Elements;
            # only what leaves the chain is materialized
            cols = ColumnBatch(records.payloads, records.sizes(),
                               records.event_times(), records.keys)
            outs = self.chain.process_cols(cols, ctx)
        else:
            outs = self.chain.process(elements_of(records), ctx)
        return [(e.payload, e.size) for e in outs]

    # -- checkpointable non-operator state -------------------------------

    def attr_snapshot(self) -> dict:
        return {a: copy.deepcopy(getattr(self, a))
                for a in self.state_attrs}

    def attr_restore(self, snap: dict) -> None:
        for a, v in snap.items():
            setattr(self, a, copy.deepcopy(v))

    # legacy helpers (batch-stage bodies still use them)
    @staticmethod
    def _unit(records) -> Optional[Any]:
        for r in reversed(records):
            if isinstance(r.payload, dict) and "unit" in r.payload:
                return r.payload["unit"]
        return None

    @staticmethod
    def _data(r) -> Any:
        return _data_of(r.payload)

    def _wrap(self, payload: Any, size: int, unit) -> tuple[Any, int]:
        return _wrapped(payload, size, unit)


# ---------------------------------------------------------------------------
# Word count pipeline (split -> count) + document analytics
# ---------------------------------------------------------------------------


def _split_fn(payload):
    d = _data_of(payload)
    text = d["text"] if isinstance(d, dict) else str(d)
    words = text.lower().split()
    return _wrapped({"words": words}, max(1, sum(map(len, words))),
                    _unit_of(payload))


class SplitQuery(Query):
    """Document -> list of words (one message per document)."""

    def ops(self):
        return [Map(_split_fn)]


def _count_fn(state, payload):
    totals = state.setdefault("totals", collections.Counter())
    d = _data_of(payload)
    words = d["words"] if isinstance(d, dict) else list(d)
    counts = collections.Counter(words)
    totals.update(counts)
    return _wrapped({"counts": dict(counts),
                     "distinct_total": len(totals)},
                    max(1, 8 * len(counts)), _unit_of(payload))


class CountQuery(Query):
    """Word-frequency counting (stateful across the run)."""

    def ops(self):
        return [StatefulMap(_count_fn)]


def _avg_len_fn(state, payload):
    sums = state.setdefault("sums", collections.Counter())
    ns = state.setdefault("ns", collections.Counter())
    d = _data_of(payload)
    topic = d.get("topic", "default") if isinstance(d, dict) else "default"
    text = d.get("text", "") if isinstance(d, dict) else str(d)
    sums[topic] += len(text.split())
    ns[topic] += 1
    avg = {t: sums[t] / ns[t] for t in sums}
    return _wrapped({"avg_words_per_topic": avg}, 8 * len(avg),
                    _unit_of(payload))


class AvgLenByTopicQuery(Query):
    """Average document length per document-topic (paper Fig. 2a job 2)."""

    def ops(self):
        return [StatefulMap(_avg_len_fn)]


# ---------------------------------------------------------------------------
# Sentiment analysis (lexicon scores via jnp)
# ---------------------------------------------------------------------------

_LEXICON = {
    "good": (0.7, 0.6), "great": (0.8, 0.75), "love": (0.5, 0.6),
    "excellent": (1.0, 1.0), "happy": (0.8, 1.0), "bad": (-0.7, 0.67),
    "terrible": (-1.0, 1.0), "hate": (-0.8, 0.9), "sad": (-0.5, 1.0),
    "awful": (-1.0, 1.0), "okay": (0.2, 0.4), "boring": (-0.4, 0.8),
}


def _sentiment_fn(payload):
    chip.init_jax()               # refuses in sweep pool workers
    import jax.numpy as jnp
    d = _data_of(payload)
    text = d["text"] if isinstance(d, dict) else str(d)
    scores = [_LEXICON[w] for w in text.lower().split() if w in _LEXICON]
    if scores:
        arr = jnp.asarray(scores, jnp.float32)
        pol, subj = [float(v) for v in jnp.mean(arr, axis=0)]
    else:
        pol, subj = 0.0, 0.0
    return _wrapped({"polarity": pol, "subjectivity": subj}, 16,
                    _unit_of(payload))


class SentimentQuery(Query):
    def ops(self):
        return [Map(_sentiment_fn)]


# ---------------------------------------------------------------------------
# Ride selection (join + groupby + window over structured data)
# ---------------------------------------------------------------------------


class RideSelectQuery(Query):
    """Best tipping areas: groupby(area) of mean tip over the window."""

    def _run(self, spe, eng, records):
        chip.init_jax()               # refuses in sweep pool workers
        import jax
        import jax.numpy as jnp
        rides = [self._data(r) for r in records]
        rides = [x for x in rides if isinstance(x, dict) and "area" in x]
        if not rides:
            return []
        areas = sorted({x["area"] for x in rides})
        aid = {a: i for i, a in enumerate(areas)}
        ids = jnp.asarray([aid[x["area"]] for x in rides], jnp.int32)
        tips = jnp.asarray([float(x.get("tip", 0.0)) for x in rides])
        sums = jax.ops.segment_sum(tips, ids, num_segments=len(areas))
        ns = jax.ops.segment_sum(jnp.ones_like(tips), ids,
                                 num_segments=len(areas))
        means = sums / jnp.maximum(ns, 1.0)
        best = int(jnp.argmax(means))
        payload = {"best_area": areas[best],
                   "mean_tip": float(means[best]),
                   "areas": {a: float(means[aid[a]]) for a in areas}}
        return [self._wrap(payload, 8 * len(areas), self._unit(records))]


# ---------------------------------------------------------------------------
# Maritime monitoring (windowed count -> external store)
# ---------------------------------------------------------------------------


class MaritimeQuery(Query):
    state_attrs = ("window_id",)

    def __init__(self, comp):
        super().__init__(comp)
        self.ports = set(comp.get("ports", ["halifax", "boston"]))
        self.window_id = 0

    def _run(self, spe, eng, records):
        from repro.core import store as store_mod
        reports = [self._data(r) for r in records]
        counts = collections.Counter(
            x["port"] for x in reports
            if isinstance(x, dict) and x.get("port") in self.ports)
        self.window_id += 1
        store_name = self.comp.get("store")
        if store_name:
            st = store_mod.lookup(store_name)
            st.remote_put(eng, spe.host, f"window{self.window_id}",
                          dict(counts))
        return [self._wrap({"window": self.window_id,
                            "counts": dict(counts)}, 8 * len(counts),
                           self._unit(records))]


# ---------------------------------------------------------------------------
# Fraud detection (linear SVM trained at init; real jnp inference)
# ---------------------------------------------------------------------------


class FraudSVMQuery(Query):
    def __init__(self, comp):
        super().__init__(comp)
        chip.init_jax()               # refuses in sweep pool workers
        import jax
        import jax.numpy as jnp
        dim = int(comp.get("dim", 8))
        rng = np.random.default_rng(0)
        # synthetic training set: anomalies have shifted mean
        n = 256
        x0 = rng.normal(0.0, 1.0, (n, dim))
        x1 = rng.normal(2.5, 1.0, (n, dim))
        X = jnp.asarray(np.concatenate([x0, x1]), jnp.float32)
        y = jnp.asarray(np.array([-1.0] * n + [1.0] * n), jnp.float32)

        def loss(w):
            margins = 1.0 - y * (X[:, :-1] @ w[:-1] + w[-1])
            return jnp.mean(jnp.maximum(margins, 0.0)) + 1e-3 * w @ w

        w = jnp.zeros((dim,), jnp.float32)
        g = jax.jit(jax.grad(loss))
        for _ in range(200):
            w = w - 0.1 * g(w)
        self.w = w
        self._score = jax.jit(
            lambda xs: xs[:, :-1] @ self.w[:-1] + self.w[-1])
        self.dim = dim

    def _run(self, spe, eng, records):
        import jax.numpy as jnp
        feats = []
        for r in records:
            d = self._data(r)
            if isinstance(d, dict) and "x" in d:
                feats.append(np.asarray(d["x"], np.float32))
        if not feats:
            return []
        # bucket-pad rows so the jitted score sees power-of-two shapes
        # (scores are per-row, so padding rows cannot perturb real rows)
        n = len(feats)
        xs = np.zeros((jit_bucket(n), self.dim), np.float32)
        xs[:n] = np.stack(feats)
        scores = np.asarray(self._score(jnp.asarray(xs)))[:n]
        payload = {"n": n,
                   "anomalies": int((scores > 0).sum()),
                   "scores": scores.tolist()}
        return [self._wrap(payload, 4 * n, self._unit(records))]


# ---------------------------------------------------------------------------
# Ocampo traffic monitoring (measured-wall query)
# ---------------------------------------------------------------------------


class TrafficMetricsQuery(Query):
    measure_wall = True

    def __init__(self, comp):
        super().__init__(comp)
        self.services = list(comp.get(
            "services", ["ftp", "web", "dns", "mail"]))
        self._sid = {s: i for i, s in enumerate(self.services)}
        self._jit_cache: dict[int, Callable] = {}

    def _metrics_fn(self, n: int):
        chip.init_jax()               # refuses in sweep pool workers
        import jax
        import jax.numpy as jnp
        if n not in self._jit_cache:
            S = len(self.services)

            @jax.jit
            def f(sids, sizes, valid):
                ones = jnp.where(valid, 1.0, 0.0)
                szs = jnp.where(valid, sizes, 0.0)
                conns = jax.ops.segment_sum(ones, sids, num_segments=S)
                bw = jax.ops.segment_sum(szs, sids, num_segments=S)
                # active users proxy: unique (user-hash) per service is
                # approximated by counts; heavy-hitter stats via sort
                order = jnp.sort(szs)[::-1]
                return conns, bw, order[: min(8, n)]

            self._jit_cache[n] = f
        return self._jit_cache[n]

    def _run(self, spe, eng, records):
        parts = stage_parts(spe)
        parts.begin("stage_build")
        pkts = [self._data(r) for r in records]
        pkts = [p for p in pkts if isinstance(p, dict) and "service" in p]
        if not pkts:
            parts.drop()
            return []
        n = jit_bucket(len(pkts))                        # pad: stable shapes
        sids = np.zeros((n,), np.int32)
        sizes = np.zeros((n,), np.float32)
        valid = np.zeros((n,), bool)
        for i, p in enumerate(pkts):
            sids[i] = self._sid.get(p["service"], 0)
            sizes[i] = float(p.get("bytes", 0))
            valid[i] = True
        unit = self._unit(records)
        parts.begin("stage_dispatch" if n in self._jit_cache
                    else "stage_compile")
        f = self._metrics_fn(n)
        conns, bw, top = f(sids, sizes, valid)
        parts.begin("stage_wait")
        conns.block_until_ready()
        parts.begin("stage_readback")
        # one host copy of both results: device_get starts every copy
        # before it waits on any (an indexed read per element would
        # dispatch a device slice and block on its copy, one by one)
        import jax
        conns, bw = jax.device_get((conns, bw))
        payload = {
            "connections": {s: float(conns[i])
                            for s, i in self._sid.items()},
            "bandwidth": {s: float(bw[i]) for s, i in self._sid.items()},
        }
        parts.end()
        return [self._wrap(payload, 8 * len(self.services), unit)]


# ---------------------------------------------------------------------------
# LM serving job (real model decode over the stream)
# ---------------------------------------------------------------------------


class LMGenerateQuery(Query):
    def __init__(self, comp):
        super().__init__(comp)
        self._built = False

    def _build(self):
        chip.init_jax()               # refuses in sweep pool workers
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config, reduce_for_smoke
        from repro.models import Model

        arch = self.comp.get("arch", "xlstm-125m")
        cfg = reduce_for_smoke(get_config(arch))
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = self.model.init_params(jax.random.key(0))
        self.gen_tokens = int(self.comp.get("genTokens", 8))
        self.max_len = int(self.comp.get("maxLen", 128))

        model, max_len = self.model, self.max_len

        @jax.jit
        def serve(params, tokens):
            B, S = tokens.shape
            logits, cache = model.prefill(params, tokens)
            # right-size the cache into the decode layout
            full = model.init_cache(B, max_len, jnp.float32)
            cache = _merge_prefill_cache(full, cache, S)
            tok = jnp.argmax(logits[:, -1], -1)

            def body(carry, pos):
                tok, cache = carry
                lg, cache = model.decode_step(params, cache, tok[:, None],
                                              pos)
                nxt = jnp.argmax(lg[:, -1], -1)
                return (nxt, cache), nxt

            (_, _), toks = jax.lax.scan(
                body, (tok, cache),
                S + jnp.arange(self.gen_tokens, dtype=jnp.int32))
            return jnp.concatenate([tok[:, None], toks.T[:, :-1]], 1)

        self._serve = serve
        self._built = True

    def _run(self, spe, eng, records):
        if not self._built:
            self._build()
        import jax.numpy as jnp
        out = []
        for r in records:
            d = self._data(r)
            if not (isinstance(d, dict) and "tokens" in d):
                continue
            toks = jnp.asarray(d["tokens"]) % self.cfg.vocab_size
            # bucket-pad the batch axis: rows decode independently, so
            # padded requests change neither outputs nor compile counts
            B = toks.shape[0]
            Bp = jit_bucket(B, min_bucket=1)
            if Bp != B:
                toks = jnp.concatenate(
                    [toks, jnp.zeros((Bp - B, toks.shape[1]),
                                     toks.dtype)], 0)
            gen = np.asarray(self._serve(self.params, toks))[:B]
            unit = _unit_of(r.payload)
            out.append(self._wrap({"generated": gen.tolist()},
                                  int(gen.size * 4), unit))
        return out


def _merge_prefill_cache(full, prefill, S: int):
    """Write prefill KV (length S) into a max_len cache; pass states thru.

    Generic splice: whichever single axis differs between the prefill
    tensor and the max-length cache is the sequence axis; the prefill
    content lands at offset 0 there.
    """
    import jax

    def merge(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        assert dst.ndim == src.ndim, (dst.shape, src.shape)
        diff = [i for i in range(dst.ndim) if dst.shape[i] != src.shape[i]]
        assert len(diff) == 1, (dst.shape, src.shape)
        idx = (0,) * dst.ndim
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                            idx)

    return jax.tree.map(merge, full, prefill)


class LMTrainQuery(Query):
    """Real LM training as a stream job: batches in, loss metrics out.

    Training state is a real optimizer state on device — deliberately
    outside ``state_attrs`` (checkpointing model weights is the job of
    ``repro.checkpoint``, not the stream-state backend).
    """

    def __init__(self, comp):
        super().__init__(comp)
        self._built = False

    def _build(self):
        chip.init_jax()               # refuses in sweep pool workers
        from repro.configs import get_config, reduce_for_smoke

        arch = self.comp.get("arch", "xlstm-125m")
        cfg = reduce_for_smoke(get_config(arch))
        self.cfg = cfg
        self._bundle = None
        self._state = None
        self._seed = int(self.comp.get("seed", 0))
        self._built = True

    def _run(self, spe, eng, records):
        if not self._built:
            self._build()
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ShapeCfg
        from repro.train import make_step_bundle
        out = []
        for r in records:
            d = self._data(r)
            if not (isinstance(d, dict) and "tokens" in d):
                continue
            toks = jnp.asarray(d["tokens"]) % self.cfg.vocab_size
            B, S = toks.shape
            if self._bundle is None:
                self._bundle = make_step_bundle(
                    self.cfg, ShapeCfg("gym", S, B, "train"))
                self._state = self._bundle.init_fn(
                    jax.random.key(self._seed))
                self._jit = jax.jit(self._bundle.step_fn,
                                    donate_argnums=(0,))
            batch = {"inputs": toks[:, :-1] if S > 1 else toks,
                     "labels": toks[:, 1:] if S > 1 else toks}
            self._state, metrics = self._jit(self._state, batch)
            unit = _unit_of(r.payload)
            out.append(self._wrap(
                {"loss": float(metrics["loss"]),
                 "step": int(metrics["step"])}, 16, unit))
        return out


class IdentityQuery(Query):
    def ops(self):
        return []                    # empty chain: pure passthrough

    def __call__(self, spe, eng, records):
        if isinstance(records, BatchView):
            # pure passthrough needs neither Records nor Elements
            return list(zip(records.payloads, records.sizes()))
        return [(e.payload, e.size) for e in elements_of(records)]


QUERIES: dict[str, type[Query]] = {
    "split": SplitQuery,
    "count": CountQuery,
    "avg_len_by_topic": AvgLenByTopicQuery,
    "sentiment": SentimentQuery,
    "ride_select": RideSelectQuery,
    "maritime": MaritimeQuery,
    "fraud_svm": FraudSVMQuery,
    "traffic_metrics": TrafficMetricsQuery,
    "lm_generate": LMGenerateQuery,
    "lm_train": LMTrainQuery,
    "identity": IdentityQuery,
}
