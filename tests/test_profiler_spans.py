"""Engine-profiler spans on the produce path and inside a stage call.

The profiler (``profile=True``) names the produce path (``produce``,
``append``, ``replicate``, ``consume``), splits a stage call's
``operator`` span into ``stage_build``, ``stage_compile`` /
``stage_dispatch``, ``stage_wait`` and ``stage_readback``, and counts
follower deliveries dropped out of order (``replicate_gap``).  Each
bucket counts its calls; the counts here are held to independent
counts of the same calls.  With profiling off nothing changes.
"""
import pytest

from repro.core import Engine, PipelineSpec
from repro.core.broker import Cluster, ReplicaLog
from repro.core.operators import Element
from repro.core.spe import SPERuntime, TrafficMetricsQuery
from repro.core.stubs import ConsumerBase, ProducerBase
from repro.core.telemetry import Profiler
from repro.sweep.results import TIMING_KEYS

STAGE_PARTS = ("stage_build", "stage_compile", "stage_dispatch",
               "stage_wait", "stage_readback")
PROFILE_KEYS = ("profile_counts", "profile_wall")


def star_spec(n_users=10):
    """Fig. 7b at a small size: users, one broker, a traffic_metrics SPE."""
    spec = PipelineSpec(mode="zk", delivery="wakeup")
    spec.add_switch("s1")
    spec.add_host("b").add_link("b", "s1", lat=0.5, bw=1000.0)
    spec.add_broker("b")
    spec.add_topic("pkts", leader="b")
    spec.add_host("spark").add_link("spark", "s1", lat=0.5, bw=1000.0)
    spec.add_spe("spark", query="traffic_metrics", inTopic="pkts",
                 window=1.0, pollInterval=0.2)
    for i in range(n_users):
        h = f"u{i}"
        spec.add_host(h).add_link(h, "s1", lat=0.5, bw=100.0)
        spec.add_producer(h, "PACKET", topic="pkts", ratePps=20.0,
                          pktBytes=256)
    return spec


def kafka_spec(linger_ms=0.0):
    """3 brokers, 3 partitions at replication 3, one producer of 300
    records (done by 3 s) and one consumer."""
    spec = PipelineSpec(mode="zk", delivery="wakeup")
    spec.add_switch("s1")
    for b in ("k0", "k1", "k2"):
        spec.add_host(b).add_link(b, "s1", lat=0.1, bw=1000.0)
        spec.add_broker(b)
    spec.add_topic("test", leader="k0", replication=3, partitions=3)
    spec.add_host("lp").add_link("lp", "s1", lat=0.1, bw=1000.0)
    spec.add_producer("lp", "SYNTHETIC", topic="test", msgSize=100,
                      rateKbps=80.0, totalMessages=300, lingerMs=linger_ms)
    spec.add_host("lc").add_link("lc", "s1", lat=0.1, bw=1000.0)
    spec.add_consumer("lc", "STANDARD", topics=["test"], pollInterval=0.1)
    return spec


def run(spec, until, seed=1, profile=True):
    if profile:
        spec.set_telemetry(profile=True)
    eng = Engine(spec, seed=seed)
    eng.run(until=until)
    return eng, eng.metrics()


def spe_of(eng) -> SPERuntime:
    return next(r for r in eng.runtimes if isinstance(r, SPERuntime))


# ---------------------------------------------------------------------------
# Produce path
# ---------------------------------------------------------------------------


def _kafka_retries():
    # the producer's link drops for 0.3 s: sends fail and are retried
    return kafka_spec().add_fault(1.0, "link_down", "lp", "s1",
                                  duration=0.3)


PRODUCE_CASES = {
    "star": (star_spec, 3.0),
    "kafka": (kafka_spec, 6.0),
    "kafka_linger": (lambda: kafka_spec(linger_ms=20.0), 6.0),
    "kafka_retries": (_kafka_retries, 6.0),
}


@pytest.mark.parametrize("case", sorted(PRODUCE_CASES))
def test_produce_counts_records_and_sends_outside_produce(case,
                                                          monkeypatch):
    make, until = PRODUCE_CASES[case]
    n = {"records": 0, "outside": 0, "appends": 0}
    inside = [False]
    produce, attempt = ProducerBase.produce, Cluster._attempt_produce
    append = Cluster._broker_append

    def counted_produce(self, *a, **k):
        n["records"] += 1
        inside[0] = True
        try:
            return produce(self, *a, **k)
        finally:
            inside[0] = False

    def counted_attempt(self, bid):
        if not inside[0]:
            n["outside"] += 1
        return attempt(self, bid)

    def counted_append(self, broker, bid):
        n["appends"] += 1
        return append(self, broker, bid)

    monkeypatch.setattr(ProducerBase, "produce", counted_produce)
    monkeypatch.setattr(Cluster, "_attempt_produce", counted_attempt)
    monkeypatch.setattr(Cluster, "_broker_append", counted_append)
    eng, m = run(make(), until)
    counts = m["profile_counts"]
    assert n["records"] > 0
    if case in ("kafka_linger", "kafka_retries"):
        assert n["outside"] > 0          # linger flushes, retries
    assert counts["produce"] == n["records"] + n["outside"]
    assert counts["append"] == n["appends"]


def test_replicate_appears_only_with_followers():
    _, star = run(star_spec(), 3.0)
    assert "replicate" not in star["profile_counts"]
    assert "replicate" not in star["profile_wall"]
    _, kafka = run(kafka_spec(), 6.0)
    # every leader append fans out to two followers, each delivery
    # timed on arrival
    assert kafka["profile_counts"]["replicate"] == \
        3 * kafka["profile_counts"]["append"]


def test_consume_counts_consumer_completions():
    _, star = run(star_spec(), 3.0)
    assert "consume" not in star["profile_counts"]   # no consumer stub
    eng, kafka = run(kafka_spec(), 6.0)
    lc = next(r for r in eng.runtimes if isinstance(r, ConsumerBase))
    assert lc.n_received == 300
    assert kafka["profile_counts"]["consume"] == \
        kafka["profile_counts"]["deliver"]


def test_produce_path_spans_nest_inside_the_event_loop():
    _, m = run(kafka_spec(), 6.0)
    wall = m["profile_wall"]
    for k in ("produce", "append", "replicate", "consume"):
        assert 0.0 < wall[k] < wall["event_fn"], k


# ---------------------------------------------------------------------------
# replicate_gap
# ---------------------------------------------------------------------------


def _count_follower_deliveries(monkeypatch, eng):
    """Live replication transfers (each schedules one follower delivery)
    and follower appends, counted apart from the program's counter."""
    n = {"sent": 0, "appended": 0}
    net = eng.net
    many = net.transfer_many

    def counted_many(src, dsts, nbytes, rng=None):
        out = many(src, dsts, nbytes, rng)
        n["sent"] += sum(1 for d, lost in out if d is not None and not lost)
        return out

    append_batch = ReplicaLog.append_batch

    def counted_append(self, records, epoch=None):
        if epoch is None:                 # the leader stamps its epoch
            n["appended"] += 1
        return append_batch(self, records, epoch=epoch)

    monkeypatch.setattr(net, "transfer_many", counted_many)
    monkeypatch.setattr(ReplicaLog, "append_batch", counted_append)
    return n


@pytest.mark.parametrize("fault", [None, (1.1, 0.2), (2.05, 0.3)])
def test_replicate_gap_counts_dropped_follower_deliveries(fault,
                                                          monkeypatch):
    spec = kafka_spec()
    if fault is not None:
        # shorter than a controller tick apart: k2 stays in the ISR
        spec.add_fault(fault[0], "link_down", "k2", "s1",
                       duration=fault[1])
    spec.set_telemetry(profile=True)
    eng = Engine(spec, seed=1)
    n = _count_follower_deliveries(monkeypatch, eng)
    eng.run(until=8.0)             # the producer is done by 3 s: all land
    gap = eng.metrics()["profile_counts"]["replicate_gap"]
    assert gap == n["sent"] - n["appended"]
    if fault is None:
        assert gap == 0
    else:
        assert gap > 0


def test_replicate_gap_needs_the_profiler():
    spec = kafka_spec().add_fault(1.1, "link_down", "k2", "s1",
                                  duration=0.2)
    _, m = run(spec, 8.0, profile=False)
    assert not any(k in m for k in PROFILE_KEYS)


# ---------------------------------------------------------------------------
# Stage parts
# ---------------------------------------------------------------------------


def _count_device_calls(monkeypatch):
    """Stage calls that reach the device: each fetches its jitted
    function once."""
    n = [0]
    fn = TrafficMetricsQuery._metrics_fn

    def counted(self, k):
        n[0] += 1
        return fn(self, k)

    monkeypatch.setattr(TrafficMetricsQuery, "_metrics_fn", counted)
    return n


def test_stage_parts_count_one_per_device_call(monkeypatch):
    calls = _count_device_calls(monkeypatch)
    # 14 users: windows of ~280 packets straddle the 256 bucket, so the
    # stage runs more than one jitted shape
    for _ in range(2):             # a second engine: a new query instance
        calls[0] = 0
        eng, m = run(star_spec(14), 6.0)
        c = m["profile_counts"]
        assert calls[0] >= 5
        for k in ("stage_build", "stage_wait", "stage_readback"):
            assert c[k] == calls[0], k
        assert c["stage_compile"] + c.get("stage_dispatch", 0) == calls[0]
        assert c["stage_compile"] == len(spe_of(eng).query._jit_cache) >= 2
        assert c["operator"] == calls[0]


def test_stage_parts_walls_fit_inside_operator():
    _, m = run(star_spec(), 4.0)
    wall = m["profile_wall"]
    parts = sum(wall.get(k, 0.0) for k in STAGE_PARTS)
    assert 0.0 < parts <= wall["operator"]


def test_stage_call_without_packets_records_no_parts():
    spec = star_spec(0).set_telemetry(profile=True)
    eng = Engine(spec, seed=0)
    rt = spe_of(eng)
    rt._process(eng, [Element({"note": 1}, 1, 0.0, None)])
    assert eng.profiler.counts == {"operator": 1}
    assert rt.stage_parts is None


def test_direct_query_call_records_no_span():
    eng = Engine(star_spec(0), seed=0)
    eng.profiler = Profiler()
    rt = spe_of(eng)
    rows = [Element({"service": "web", "bytes": 3}, 3, 0.0, None)
            for _ in range(20)]
    out = rt.query(rt, eng, rows)
    assert out and out[0][0]["connections"]["web"] == 20.0
    assert eng.profiler.counts == {} and eng.profiler.wall == {}
    # the same rows through the runtime: the operator span and its parts
    rt._process(eng, rows)
    assert set(eng.profiler.counts) == {"operator", "stage_build",
                                        "stage_dispatch", "stage_wait",
                                        "stage_readback"}


# ---------------------------------------------------------------------------
# Profiling off
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make,until", [(star_spec, 3.0),
                                        (_kafka_retries, 6.0)])
def test_profiler_observes_only(make, until):
    eng_off, off = run(make(), until, profile=False)
    eng_on, on = run(make(), until)
    assert not any(k in off for k in PROFILE_KEYS)
    skip = set(TIMING_KEYS) | set(PROFILE_KEYS) | {
        "telemetry_samples", "telemetry_series", "telemetry_digest",
        "stage_spans", "stage_digest", "lineage_records", "flight_events",
        "engine_events", "events_scheduled"}
    assert {k: v for k, v in off.items() if k not in skip} == \
        {k: v for k, v in on.items() if k not in skip}
    out_off = [r.outputs for r in eng_off.runtimes if hasattr(r, "outputs")]
    out_on = [r.outputs for r in eng_on.runtimes if hasattr(r, "outputs")]
    assert out_off == out_on
