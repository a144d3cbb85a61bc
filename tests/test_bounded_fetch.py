"""A bounded subscriber's fetch cycle splits its byte budget fairly.

Under the ``pause`` policy one fetch cycle divides the budget the
subscriber has when it starts over the owned partitions with committed
rows (rule: ``Cluster._fetch``).  Held here:

- one cycle equals a plain reference planned from lists of record sizes,
  on seeded random sizes, budgets and queue states;
- the Kreps cluster with a consumer at 0.8x the offered rate
  (``bench/kinds/kafka_bounded.py``) serves every partition: gap-free,
  duplicate-free offset prefixes, admitted counts within one share of
  each other, the strict bound, nothing shed;
- a client that still reads a deposed leader, whose log differs from
  the plan's, takes no more than the plan's bytes, and a take past the
  bound raises;
- a bounded 6-partition consumer under overload keeps its recorded
  metrics, event stream and views;
- a run with no bounded subscriber keeps its metrics fingerprint.
"""
import hashlib
import json
import math
import os
import sys

import numpy as np
import pytest

from _pins import digest, events_digest, metrics_digest
from repro.core import Engine, PipelineSpec
from repro.core.broker import Record
from repro.sweep.results import TIMING_KEYS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# ---------------------------------------------------------------------------
# The plain reference: one cycle from lists of record sizes
# ---------------------------------------------------------------------------


def reference_cycle(backlogs, budget, state, cap, queued):
    """One byte-budgeted fetch cycle, from plain lists.

    ``backlogs[p]``: sizes of partition ``p``'s committed records past
    its offset, partitions in assignment order; ``budget``: free queue
    bytes; ``state``: ``(rotation point, partitions owed)``; ``cap``: the
    response cap; ``queued``: bytes already in the queue.  Returns the
    records and bytes each partition takes, the next state, and whether
    the subscriber is left starved.
    """
    n = len(backlogs)
    rot, owed = state
    rot %= n
    rotated = [(rot + i) % n for i in range(n)]
    order = list(owed) + [p for p in rotated if p not in owed]
    rows, taken = [0] * n, [0] * n
    left = budget

    def rest(p):
        return backlogs[p][rows[p]:]

    first = [p for p in owed if rest(p)] or \
        [p for p in order if rest(p)][:1]
    hold = False
    for i, p in enumerate(first):
        size = rest(p)[0]
        if size <= left:
            rows[p], taken[p] = 1, size
            left -= size
        elif i == 0:
            if queued == 0:
                rows[p], taken[p] = 1, size
            hold = True
            break
    if not hold:
        with_rows = [p for p in order if rest(p)]
        for i, p in enumerate(with_rows):
            share = left // (len(with_rows) - i)
            room = min(cap - taken[p], share)
            for size in rest(p):
                if size > room:
                    break
                rows[p] += 1
                taken[p] += size
                room -= size
                left -= size
    passed = [p for p in order if backlogs[p] and not rows[p]]
    left_rows = [p for p in order if rows[p] < len(backlogs[p])]
    starved = bool(passed) and queued + sum(taken) > 0
    if left_rows:
        state = ((rot + 1) % n, tuple(passed))
    return rows, taken, state, starved


def test_reference_splits_evenly_and_rotates_the_remainder():
    # 6 partitions of 100 B records, 50,000 B free: the lead's first
    # record, then 83 records each; the bytes the first leave pass on
    # to the last walked
    backlog = [[100] * 600 for _ in range(6)]
    rows, taken, state, starved = reference_cycle(backlog, 50_000,
                                                  (0, ()), 1 << 20, 0)
    assert rows == [84, 83, 83, 83, 83, 84]
    assert sum(taken) == 50_000 and state == (1, ()) and not starved
    rows, _, state, _ = reference_cycle(backlog, 50_000, state, 1 << 20, 0)
    assert rows == [84, 84, 83, 83, 83, 83] and state == (2, ())
    # shares smaller than a record: the partitions passed over are owed
    # and take their next record first in the next cycle
    backlog = [[900, 900] for _ in range(3)]
    rows, _, state, starved = reference_cycle(backlog, 2_000, (0, ()),
                                              1 << 20, 500)
    assert rows == [1, 0, 1] and state == (1, (1,)) and starved
    rows, _, state, _ = reference_cycle(backlog, 2_000, state, 1 << 20,
                                        500)
    assert rows == [1, 1, 0] and state == (2, (2,))
    # the first record owed is larger than the budget: nothing is taken
    # (hold), or into an empty queue it goes alone (overshoot)
    backlog = [[1_200, 10], [10]]
    rows, _, state, starved = reference_cycle(backlog, 1_000, (0, ()),
                                              1 << 20, 500)
    assert rows == [0, 0] and state == (1, (0, 1)) and starved
    rows, taken, _, starved = reference_cycle(backlog, 1_000, (0, ()),
                                              1 << 20, 0)
    assert rows == [1, 0] and taken == [1_200, 0] and starved


# ---------------------------------------------------------------------------
# _fetch against the reference on a small cluster
# ---------------------------------------------------------------------------


def small_cluster(n_parts, bound, cap, replication=1):
    spec = PipelineSpec(delivery="wakeup")
    spec.add_switch("s1")
    for h in ("b0", "b1", "c"):
        spec.add_host(h).add_link(h, "s1", lat=0.1, bw=1000.0)
    spec.add_broker("b0", fetch_bytes=cap)
    spec.add_broker("b1")
    spec.add_topic("t", leader="b0", partitions=n_parts,
                   replication=replication)
    spec.add_consumer("c", "STANDARD", topics=["t"], pollInterval=0.1,
                      queueBytes=bound, shedPolicy="pause")
    eng = Engine(spec, seed=1)
    (sub,) = [r for r in eng.runtimes if getattr(r, "topics", None)]
    eng.cluster.subscribe(sub, "t")     # what starting the loop does
    return eng, sub


def append(eng, part, sizes, seq, broker=None):
    cl = eng.cluster
    broker = broker or cl.topics["t"].parts[part].leader
    log = cl.logs[broker][("t", part)]
    log.append_batch([Record(next(seq), "t", None, int(s), 0.0, "p",
                             partition=part) for s in sizes], epoch=0)
    log.hw = log.leo


@pytest.mark.parametrize("seed", range(8))
def test_fetch_cycle_matches_reference(seed):
    rng = np.random.default_rng(seed)
    n_parts = int(rng.integers(1, 7))
    bound = int(rng.choice([600, 4_000, 50_000]))
    cap = int(rng.choice([1 << 20, 3_000]))
    eng, sub = small_cluster(n_parts, bound, cap)
    cl = eng.cluster
    seq = iter(range(1, 1 << 30))
    pending = [[] for _ in range(n_parts)]
    offs = [0] * n_parts
    state = (0, ())
    kinds = set()
    for _ in range(60):
        for p in range(n_parts):
            if rng.random() < 0.6:
                sizes = rng.integers(20, 1_500, int(rng.integers(0, 40)))
                append(eng, p, sizes, seq)
                pending[p] += [int(s) for s in sizes]
        queued = int(rng.choice([0, 0, int(rng.integers(0, bound))]))
        sub._q_used = queued
        sub._bp_starved = False
        owed = state[1]
        want_rows, want_bytes, state, starved = reference_cycle(
            pending, bound - queued, state, cap, queued)
        cl.fetch(sub, "t")
        got = [cl._consumer_offsets[("t", p, sub.name)] - offs[p]
               for p in range(n_parts)]
        assert got == want_rows
        assert sub._q_used - queued == sum(want_bytes)
        assert sub._bp_starved == starved
        assert cl._fair_state.get(("t", sub.name), (0, ())) == state
        assert queued + sum(want_bytes) <= bound or (
            queued == 0 and sum(r > 0 for r in want_rows) == 1
            and max(want_rows) == 1), "only a lone record may overshoot"
        # what is owed is taken first while the budget holds it
        heads = sum(pending[p][0] for p in owed if pending[p])
        if owed and heads <= bound - queued:
            assert all(got[p] for p in owed if pending[p])
            kinds.add("repaid")
        kinds.update({"took"} if sum(got) else set())
        kinds.update({"starved"} if starved else set())
        for p in range(n_parts):
            offs[p] += got[p]
            pending[p] = pending[p][got[p]:]
    assert "took" in kinds


def test_take_from_a_deposed_leader_stays_within_the_plan():
    # b1 leads now and its log plans the cycle; the client still reads
    # b0, which believes it leads, and whose records at the same offsets
    # are nine times larger
    eng, sub = small_cluster(1, 5_000, 1 << 20, replication=2)
    cl = eng.cluster
    seq = iter(range(1, 1 << 30))
    append(eng, 0, [900] * 100, seq, broker="b0")
    append(eng, 0, [100] * 100, seq, broker="b1")
    cl.topics["t"].parts[0].leader = "b1"
    cl._client_meta[(sub.name, "t", 0)] = "b0"
    assert cl._belief[("b0", "t", 0)][0]
    cl.fetch(sub, "t")
    # the plan: 50 records, 5,000 B; b0 holds five of them within it
    assert cl._consumer_offsets[("t", 0, sub.name)] == 5
    assert sub._q_used == 4_500


def test_a_take_past_the_bound_raises():
    eng, sub = small_cluster(1, 5_000, 1 << 20)
    sub.bp_reserve(6_000)       # a lone record into an empty queue
    sub.bp_drain(eng, 6_000)
    sub.bp_reserve(4_000)
    sub.bp_reserve(1_000)
    assert sub._q_used == 5_000
    sub.bp_drain(eng, 1_000)
    with pytest.raises(RuntimeError, match="queueBytes 5000"):
        sub.bp_reserve(1_001)
    assert sub._q_used == 4_000


# ---------------------------------------------------------------------------
# The Kreps cluster with a consumer that cannot keep up
# ---------------------------------------------------------------------------

RATE = 800.0            # the load consumer's records a second


def overload(profile=True, until=6.0, seed=3):
    from bench import catalog

    cell = {"config": json.load(open(os.path.join(
        ROOT, "bench", "configs", "kreps2014_overload.json"))),
        "traffic": {}}
    p = {**catalog.params(cell), "n_users": 2}
    spec = catalog.kind("kafka_bounded").build(p, cell["traffic"])
    if profile:
        spec.set_telemetry(profile=True)
    eng = Engine(spec, seed=seed)
    (lc,) = [r for r in eng.runtimes
             if getattr(r, "topics", None) == ["test"]]
    views, done = [], [0]
    on, handle = lc.on_records, lc.handle

    def on_records(e, recs):
        views.append((recs.partition, recs.msg_ids()))
        return on(e, recs)

    def counted(e, recs):
        done[0] += len(recs)
        return handle(e, recs)

    lc.on_records, lc.handle = on_records, counted
    eng.run(until=until)
    return eng, lc, views, done[0], p


def test_overloaded_consumer_serves_every_partition_fairly():
    eng, lc, views, processed, p = overload()
    cl, m = eng.cluster, eng.metrics()
    parts, bound = int(p["partitions"]), int(p["consumer_queue_bytes"])
    admitted = []
    for part in range(parts):
        log = cl.logs[cl.topics["test"].parts[part].leader][("test", part)]
        got = np.concatenate([ids for q, ids in views if q == part])
        off = cl._consumer_offsets[("test", part, lc.name)]
        # a gap-free, duplicate-free prefix of the partition's log
        assert got.tolist() == log.batch.msg_id[:off].tolist()
        admitted.append(off)
    assert max(admitted) - min(admitted) <= math.ceil(
        bound / (parts * int(p["record_bytes"])))
    assert 0 < m["queue_peak_bytes"] <= bound
    assert m["records_shed"] == 0
    assert m["backpressure_pauses"] > 0
    assert m["profile_counts"]["fetch_capped"] > 0
    # the consumer works at its own rate, within one budget of the fluid
    # bound, and the lag grows
    produced = m["records_produced"] - sum(
        1 for x in eng.monitor.msgs.values() if x.topic == "pkts")
    fluid = min(produced, RATE * eng.now)
    assert abs(processed - fluid) <= bound / int(p["record_bytes"])
    assert sum(admitted) < produced - 500


# (metrics with profile_counts, monitor event stream, the load
# consumer's views, records processed), recorded at commit 1e45a58
OVERLOAD_PIN = ("f6f59e21489d5bb6", "0ae8a635b6548ec7",
                "7ee04641f2426140", 3076)


def test_bounded_multi_partition_fused_equals_legacy():
    eng, _, views, processed, _ = overload(until=4.0)
    m = eng.metrics()
    assert (metrics_digest(m), events_digest(eng.monitor),
            digest(views),
            processed) == OVERLOAD_PIN
    for k in ("backpressure", "fetch_capped", "fetch_ctl", "fetch_take",
              "deliver"):
        assert m["profile_counts"][k] > 0, k


def test_bounded_counters_absent_with_profiling_off():
    eng, *_ = overload(profile=False, until=2.0)
    assert "profile_counts" not in eng.metrics()


# ---------------------------------------------------------------------------
# No bounded subscriber: nothing moves
# ---------------------------------------------------------------------------

# sha256 of the metrics below, recorded before the fair split existed
UNBOUNDED_FINGERPRINT = (
    "a29506a8f58ab89761e05049baebf903e0cf34ec3a401c2f6a1b214957eafda1")


def unbounded_metrics():
    """3 brokers, 6 partitions at replication 3, 1,000 records/s of
    100 B, one slow but unbounded consumer and a group of two, with the
    profiler on (its counts join the fingerprint)."""
    spec = PipelineSpec(mode="zk", delivery="wakeup")
    spec.add_switch("s1")
    for h in ("k0", "k1", "k2", "lp", "lc", "g0", "g1"):
        spec.add_host(h, n_cores=1 if h == "lc" else 8)
        spec.add_link(h, "s1", lat=0.1, bw=1000.0)
    for b in ("k0", "k1", "k2"):
        spec.add_broker(b)
    spec.add_topic("test", leader="k0", replication=3, partitions=6)
    spec.add_producer("lp", "SYNTHETIC", topic="test", msgSize=100,
                      rateKbps=800.0)
    spec.add_consumer("lc", "STANDARD", topics=["test"], pollInterval=0.1,
                      perRecordCost=0.0011998)
    for g in ("g0", "g1"):
        spec.add_consumer(g, "STANDARD", topics=["test"], group="grp",
                          pollInterval=0.1)
    spec.set_telemetry(profile=True)
    eng = Engine(spec, seed=11)
    eng.run(until=3.0)
    return {k: v for k, v in eng.metrics().items() if k not in TIMING_KEYS}


def test_unbounded_fingerprint_unchanged():
    m = unbounded_metrics()
    assert "fetch_capped" not in m["profile_counts"]
    assert "backpressure" not in m["profile_counts"]
    blob = json.dumps(m, sort_keys=True, default=repr)
    assert hashlib.sha256(blob.encode()).hexdigest() == \
        UNBOUNDED_FINGERPRINT
