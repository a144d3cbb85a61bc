"""Digests that pinned runs are compared by, and the queue-phase switch.

A pinned run is held to values recorded from an earlier run of the same
scenario: the sha256 (first 16 hex digits) of canonical JSON over its
metrics without the wall-clock keys — the event-loop counters included —
and over its monitor's ``(kind, t)`` event stream.

:class:`~repro.core.calqueue.CalendarQueue` is a heap until more than
``PROMOTE_N`` events are outstanding, then a bucketed wheel.  No pinned
scenario comes near that count, so a test that must run the wheel under a
full engine lowers the threshold to 0 with ``queue_phase``; the pop order,
and so every pinned value, must not change.  A monkeypatch does not cross
processes: run such an engine in the test's own process.
"""
import hashlib
import json

from repro.core.calqueue import CalendarQueue
from repro.sweep.results import TIMING_KEYS

# "calendar": the queue as the engine builds it (heap phase at these
# sizes); "wheel": the bucketed wheel from the first push
PHASES = ("calendar", "wheel")


def digest(obj) -> str:
    blob = json.dumps(obj, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def metrics_digest(m: dict) -> str:
    return digest({k: v for k, v in m.items() if k not in TIMING_KEYS})


def events_digest(mon) -> str:
    return digest([(e["kind"], e["t"]) for e in mon.events])


def deliveries_digest(mon) -> str:
    """Which subscriber got each message, and when."""
    return digest([(mid, sorted(m.deliveries.items()))
                   for mid, m in sorted(mon.msgs.items())])


def queue_phase(monkeypatch, phase: str) -> None:
    assert phase in PHASES, phase
    if phase == "wheel":
        monkeypatch.setattr(CalendarQueue, "PROMOTE_N", 0)
