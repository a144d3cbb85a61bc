"""Delivery cohorts: pinned runs of the fetch schedule.

A fetch cycle posts one deliver event per (subscriber, fetch cycle,
landing time) and ``_notify`` wakes every parked subscriber of a topic
through one cohort event (``Engine.schedule_cohort``).  Each scenario
below is held to values recorded from the same scenario at commit
1e45a58 (``PINS``): engine and scheduled event counts, and digests of
every metric but the wall clock, of the monitor's ``(kind, t)`` event
stream and of each message's delivery map (see ``tests/_pins.py``).
That commit also ran a per-partition, per-waiter schedule and held it
equal to these runs on every metric outside the event-loop counters.
The scenarios cover every hard configuration the broker supports:
both delivery modes on both phases of the calendar queue, record mode,
consumer groups mid-rebalance, bounded queues under each shed policy,
and exactly-once recovery under chaos.

Also covers the memoized ``assigned_partitions`` rebalance regression
and the ``kernels/cohort.py`` helpers.
"""
import numpy as np
import pytest

from _pins import (PHASES, deliveries_digest, events_digest, metrics_digest,
                   queue_phase)
from repro.core import Engine, PipelineSpec
from repro.kernels import cohort
from repro.sweep import SweepSpec, run_sweep
from repro.sweep.scenarios import build_scenario


def pinned(m, mon):
    """What a pinned run is compared by."""
    return (m["engine_events"], m["events_scheduled"], metrics_digest(m),
            events_digest(mon), deliveries_digest(mon))


def run_scenario(p, seed=0):
    eng = Engine(build_scenario(p), seed=seed)
    mon = eng.run(until=float(p["horizon"]))
    return eng, mon, eng.metrics()


def run_pinned(name, p, seed=0):
    eng, mon, m = run_scenario(p, seed)
    assert pinned(m, mon) == PINS[name], name
    return eng, mon, m


# a scenario where cohorts actually form: multiple partitions per topic
# (deliver coalescing) and multiple wakeup subscribers per topic
# (notify coalescing), over a WAN with replication
BASE = {
    "topology": "geo_wan", "n_hosts": 10, "n_brokers": 3,
    "replication": 2, "n_topics": 3, "n_producers": 3,
    "partitions": 4, "rate_kbps": 64.0, "msg_size": 512,
    "horizon": 8.0, "seed": 0,
}

# slow consumers + tiny queues force the degradation machinery
BOUNDED = {**BASE, "delivery": "wakeup", "rate_kbps": 256.0,
           "queue_bytes": 2 << 10, "consumer_cost": 0.1,
           "horizon": 10.0}

# a checkpointed exactly-once SPE, seeded chaos (flaps, gray loss, slow
# hosts, crash/heal) and an spe_down fault
EXACTLY_ONCE = {**BASE, "delivery": "wakeup", "windowed": 1,
                "window_s": 1.0, "time_mode": "event", "et_jitter_s": 0.2,
                "checkpoint_interval": 2.0, "spe_semantics": "exactly_once",
                "fault": "spe_down", "fault_at": 4.0, "fault_duration": 2.0,
                "chaos": 1, "horizon": 12.0}

# (engine_events, events_scheduled, metrics, event stream, delivery
# maps), recorded at commit 1e45a58
PINS = {
    "wakeup": (3518, 3525, "81ee7251f093216d", "4f53cda18c2baa0c",
               "25563ebbe2d7e661"),
    "poll": (3189, 3199, "4ebcfcc6075dad5f", "4f53cda18c2baa0c",
             "55770d7f6929972f"),
    "multi_partition_poll": (8650, 8666, "fdafbd72bb70710d",
                             "4f53cda18c2baa0c", "29e8f6114e3f81bc"),
    "record_mode": (3518, 3525, "5689e20da5fc3c91", "4f53cda18c2baa0c",
                    "25563ebbe2d7e661"),
    "exactly_once_chaos": (5204, 5218, "936b4b01eae686a6", "d098d9bc6a5d3ed6",
                           "b8647071abd28079"),
    "bounded/pause": (8363, 8391, "94ec92c5080726d8", "4f53cda18c2baa0c",
                      "e4de730af6d544e2"),
    "bounded/drop_oldest": (7501, 7529, "04aebe5b9c43e876", "338b0b8a519721b1",
                            "cc532b30be4e2fb6"),
    "bounded/drop_newest": (7501, 7529, "04aebe5b9c43e876", "338b0b8a519721b1",
                            "cc532b30be4e2fb6"),
    "bounded/sample": (7501, 7529, "240bdfb622da26d1", "338b0b8a519721b1",
                       "cc532b30be4e2fb6"),
    "group/wakeup": (875, 876, "dea9dded3ed7cce1", "35c36f34aac96388",
                     "f1a1d6c4e43d607b"),
    "group/poll": (1298, 1301, "37efbfd2c90ace73", "35c36f34aac96388",
                   "c51bff539b929858"),
}


# ---------------------------------------------------------------------------
# Core grid: delivery x queue phase
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheduler", PHASES)
@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_parity_across_delivery_and_scheduler(delivery, scheduler,
                                              monkeypatch):
    queue_phase(monkeypatch, scheduler)
    eng, _, _ = run_pinned(delivery, {**BASE, "delivery": delivery})
    # the default queue never leaves its heap phase at this size
    assert (eng._q._heap is None) == (scheduler == "wheel")


def test_multi_partition_deliver_cohorts_shrink_poll_events():
    # with 4 partitions per topic, cohorts form whenever several
    # partitions land together
    _, _, m = run_pinned("multi_partition_poll",
                         {**BASE, "delivery": "poll", "rate_kbps": 256.0})
    assert m["records_delivered"] > 0


def test_record_mode_parity():
    # columnar=0 materializes per-row Records at fetch; materialization
    # happens at fetch-take, delivery at landing: the counts differ only
    # by records still in flight at the horizon
    _, _, m = run_pinned("record_mode",
                         {**BASE, "delivery": "wakeup", "columnar": 0})
    assert m["record_objects_materialized"] >= m["records_delivered"] > 0


# ---------------------------------------------------------------------------
# Consumer groups mid-rebalance
# ---------------------------------------------------------------------------


def group_spec(delivery="wakeup"):
    spec = PipelineSpec(delivery=delivery)
    spec.add_switch("s1")
    spec.add_host("b1").add_link("b1", "s1", lat=1.0, bw=100.0)
    spec.add_broker("b1")
    spec.add_topic("t", leader="b1", partitions=4)
    spec.add_host("p").add_link("p", "s1", lat=1.0, bw=100.0)
    spec.add_producer("p", "SYNTHETIC", topics=["t"], rateKbps=40.0,
                      msgSize=500, totalMessages=150, nKeys=8)
    for h in ("c0", "c1"):
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=100.0)
        spec.add_consumer(h, "STANDARD", topics=["t"], group="g",
                          pollInterval=0.2)
    spec.add_fault(10.0, "host_down", "c1", duration=12.0)
    return spec


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_group_rebalance_parity(delivery):
    # a member dies and recovers mid-run: partitions move at committed
    # offsets through two rebalances.  The fetch cycle reads partitions
    # through the generation-checked memo, so the event stream
    # (rebalances included), per-message delivery maps and group lag
    # must match the recorded run exactly
    eng = Engine(group_spec(delivery), seed=9)
    mon = eng.run(until=60.0)
    m = eng.metrics()
    assert pinned(m, mon) == PINS["group/" + delivery]
    assert m["group_rebalances"] >= 2


def test_assigned_partitions_memo_tracks_rebalance_generation():
    # the memo must serve the *current* assignment after every
    # generation bump — never a stale tuple
    eng = Engine(group_spec(), seed=9)
    eng.run(until=60.0)
    cluster = eng.cluster
    consumers = list(cluster.subs["t"])
    gs = cluster.groups[("g", "t")]
    assert gs.generation >= 3          # initial assign + fail + recover
    seen = []
    for c in consumers:
        a1 = cluster.assigned_partitions(c, "t")
        a2 = cluster.assigned_partitions(c, "t")
        assert isinstance(a1, tuple)
        assert a1 is a2                # memo hit returns the cached tuple
        assert list(a1) == gs.assignment.get(c.name, [])
        assert list(a1) == sorted(a1)
        seen.extend(a1)
    assert sorted(seen) == [0, 1, 2, 3]     # disjoint cover, no overlap
    # the cache entry is pinned to the live generation
    for c in consumers:
        assert cluster._ap_cache[(c.name, "t")][0] == gs.generation


def test_solo_consumers_share_the_topic_partition_tuple():
    # implicit solo groups never rebalance: every call returns the
    # topic's precomputed partition tuple, no cache entry needed
    eng, _, _ = run_scenario({**BASE, "delivery": "poll"})
    cluster = eng.cluster
    for topic, consumers in cluster.subs.items():
        for c in consumers:
            a1 = cluster.assigned_partitions(c, topic)
            assert a1 is cluster.assigned_partitions(c, topic)
            assert list(a1) == list(range(len(cluster.topics[topic].parts)))


# ---------------------------------------------------------------------------
# Bounded queues: backpressure pause + the three shed policies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["pause", "drop_oldest",
                                    "drop_newest", "sample"])
def test_bounded_queue_parity(policy):
    # shed/pause decisions happen at admission, per view in fetch order,
    # so every degradation counter must match the recorded run
    _, _, m = run_pinned("bounded/" + policy,
                         {**BOUNDED, "shed_policy": policy})
    if policy == "pause":
        assert m["backpressure_pauses"] > 0
        assert m["records_shed"] == 0
    else:
        assert m["records_shed"] > 0
        assert m["bytes_shed"] > 0
    assert m["queue_peak_bytes"] > 0


# ---------------------------------------------------------------------------
# exactly_once recovery under chaos
# ---------------------------------------------------------------------------


def test_exactly_once_recovery_under_chaos_parity():
    # recovery replays from the snapshot; the replay/recovery accounting
    # must match the recorded run
    _, _, m = run_pinned("exactly_once_chaos", EXACTLY_ONCE)
    assert m["spe_recoveries"] >= 1
    assert m["checkpoint_count"] > 0
    assert m["windows_fired"] > 0
    assert m["chaos_faults"] >= 1


# ---------------------------------------------------------------------------
# Cross-process fingerprint identity
# ---------------------------------------------------------------------------


def test_fused_fingerprint_identical_across_worker_processes():
    # the sweep cache mixes rows from different spawned workers: the
    # cohort hot path must hash identically inline and in a worker pool
    grid = SweepSpec(
        name="fused_xproc",
        axes={"delivery": ["poll", "wakeup"]},
        base={**BASE, "horizon": 5.0})
    inline = run_sweep(grid, workers=1, cache_dir=None)
    pooled = run_sweep(grid, workers=2, cache_dir=None)
    assert inline.fingerprint() == pooled.fingerprint()


# ---------------------------------------------------------------------------
# kernels/cohort.py helpers
# ---------------------------------------------------------------------------


def test_pane_starts_matches_scalar_pane_start():
    times = [0.0, 0.49, 0.5, 0.999, 1.0, 17.3, 1e6 + 0.25,
             3.5000000000000004]
    for size in (0.5, 1.0, 0.25):
        vec = cohort.pane_starts(times, size)
        assert vec.dtype == np.float64
        assert vec.tolist() == [cohort.pane_start(t, size) for t in times]


def test_group_spans_small_and_vector_paths_agree():
    rng = np.random.default_rng(0)
    for n in (0, 1, 2, 5, 31, 32, 33, 200):
        vals = rng.integers(0, 4, size=n).tolist()
        spans = cohort.group_spans(vals)
        # reference: consecutive equal runs, covering [0, n) in order
        ref, i = [], 0
        while i < len(vals):
            j = i
            while j < len(vals) and vals[j] == vals[i]:
                j += 1
            ref.append((i, j))
            i = j
        assert spans == ref
        assert all(len(set(vals[lo:hi])) == 1 for lo, hi in spans)


def test_group_spans_respects_float_landing_times():
    # equal-t_land runs must group exactly; near-equal floats must not
    vals = [1.0, 1.0, 1.0 + 1e-12, 2.0, 2.0]
    assert cohort.group_spans(vals) == [(0, 2), (2, 3), (3, 5)]


def test_int_tallies_sums_per_key_in_python_ints():
    hosts = ["a", "b", "a", "c", "b", "a"]
    nbytes = [1, 10, 100, 1000, 10000, 100000]
    got = cohort.int_tallies(hosts, nbytes)
    assert got == {"a": 100101, "b": 10010, "c": 1000}
    assert all(type(v) is int for v in got.values())
    assert cohort.int_tallies([], []) == {}
