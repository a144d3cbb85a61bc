"""Calendar-queue scheduler: exact heap-order parity + engine parity.

The queue is the engine's only event queue, so its contract is strict:
the pop sequence must be *bit-identical* to ``heapq`` under the same
``(t, seq)`` entries — in compact (heap) mode, on the bucketed wheel,
and across the adaptive promotion between them.  The fuzz tests drive
all three through a schedule-heavy workload shaped like the engine's
(zero-delay wakeups, near-future timers, a far tail of delivery-timeout
retries); the engine tests pin that a full simulation is event-stream
identical on the queue's heap phase and on its wheel phase.
"""
import heapq
import random

import pytest

from _pins import queue_phase
from repro.core import Engine, PipelineSpec
from repro.core.calqueue import CalendarQueue


class _H:
    __slots__ = ("cancelled",)

    def __init__(self):
        self.cancelled = False


class _HeapRef:
    """The reference order: one binary heap over ``(t, seq)``."""

    def __init__(self):
        self._q = []

    def push(self, t, seq, h):
        heapq.heappush(self._q, (t, seq, h))

    def pop(self):
        return heapq.heappop(self._q) if self._q else None


def _drive(q, *, n=20_000, seed=0, preload=300, zero_frac=0.2,
           far_frac=0.05):
    """Engine-shaped workload; returns the full (t, seq) pop trace."""
    rng = random.Random(seed)
    now, seq, h, out = 0.0, 0, _H(), []
    for _ in range(preload):
        seq += 1
        q.push(now + rng.expovariate(2.0), seq, h)
    for _ in range(n):
        e = q.pop()
        out.append(e[:2])
        now = e[0]
        r = rng.random()
        if r < zero_frac:
            d = 0.0                       # wakeup notifications
        elif r < 1.0 - far_frac:
            d = rng.expovariate(4.0)      # near-future timers
        else:
            d = 20.0 + rng.random() * 200.0   # delivery-timeout tail
        seq += 1
        q.push(now + d, seq, h)
    while True:                           # drain to empty
        e = q.pop()
        if e is None:
            break
        out.append(e[:2])
    return out


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_pop_order_identical_to_heap_all_modes(seed):
    ref = _drive(_HeapRef(), seed=seed)
    wheel = _drive(CalendarQueue(promote_n=0), seed=seed)      # wheel-only
    adaptive = _drive(CalendarQueue(), seed=seed)              # compact
    promoted = _drive(CalendarQueue(promote_n=50), seed=seed)  # promotes
    assert wheel == ref
    assert adaptive == ref
    assert promoted == ref
    assert len(ref) > 20_000


def test_equal_times_keep_fifo_seq_order():
    # many entries at the exact same timestamp must pop in push order
    q = CalendarQueue(promote_n=0)
    h = _H()
    for seq in range(1, 200):
        q.push(5.0, seq, h)
    got = [q.pop()[1] for _ in range(199)]
    assert got == list(range(1, 200))


def test_far_future_overflow_and_rotation():
    # entries far beyond the wheel horizon come back in order, across
    # several window rotations and an idle fast-forward gap
    q = CalendarQueue(bucket_s=0.01, n_buckets=16, promote_n=0)  # 0.16 s
    rng = random.Random(3)
    h = _H()
    pushed = []
    for seq in range(1, 500):
        t = rng.choice([rng.random() * 0.1,          # in-window
                        rng.random() * 5.0,          # a few windows out
                        1000.0 + rng.random()])      # idle gap jump
        q.push(t, seq, h)
        pushed.append((t, seq))
    assert [q.pop()[:2] for _ in range(499)] == sorted(pushed)
    assert q.pop() is None


def test_len_tracks_entries():
    q = CalendarQueue(promote_n=4)
    h = _H()
    for seq in range(1, 11):
        q.push(float(seq), seq, h)       # crosses the promotion point
    assert len(q) == 10
    for i in range(10):
        assert q.pop() is not None
        assert len(q) == 9 - i
    assert q.pop() is None and len(q) == 0


def test_make_queue_kinds():
    # one event queue: no option selects another
    with pytest.raises(TypeError):
        PipelineSpec(scheduler="heap")
    with pytest.raises(TypeError):
        PipelineSpec(fetch_mode="legacy")
    with pytest.raises(TypeError):
        Engine(_spe_spec(), seed=0, scheduler="heap")


# ---------------------------------------------------------------------------
# Engine-level parity: full simulations bit-identical across queue phases
# ---------------------------------------------------------------------------


def _spe_spec():
    docs = ["to be or not to be", "be the change", "stream all things"]
    spec = PipelineSpec(delivery="wakeup")
    spec.add_switch("s1")
    for h in ["b", "h1", "h2", "h3"]:
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=1000.0)
    spec.add_broker("b")
    for t in ["raw", "words"]:
        spec.add_topic(t, leader="b")
    spec.add_producer("h1", "DIRECTORY", topic="raw", docs=docs,
                      totalMessages=6, interval=0.3)
    spec.add_spe("h2", query="split", inTopic="raw", outTopic="words",
                 pollInterval=0.05)
    spec.add_consumer("h3", "METRICS", topic="words", pollInterval=0.05)
    return spec


def test_engine_event_streams_identical_across_schedulers(monkeypatch):
    runs = {}
    for phase in ("calendar", "wheel"):
        queue_phase(monkeypatch, phase)
        eng = Engine(_spe_spec(), seed=0)
        mon = eng.run(until=15.0)
        sink = [rt for rt in eng.runtimes
                if rt.name.startswith("consumer")][0]
        m = eng.metrics()
        m.pop("wall_s")
        runs[phase] = (m, list(mon.events), list(sink.payloads))
    assert runs["wheel"] == runs["calendar"]
    assert runs["wheel"][2], "sink must receive results"


def test_engine_uses_calendar_by_default(monkeypatch):
    eng = Engine(_spe_spec(), seed=0)
    assert isinstance(eng._q, CalendarQueue)
    assert eng._q._heap is not None        # starts in its heap phase
    queue_phase(monkeypatch, "wheel")
    eng2 = Engine(_spe_spec(), seed=0)
    assert isinstance(eng2._q, CalendarQueue)
    assert eng2._q._heap is None           # on the wheel from the start
