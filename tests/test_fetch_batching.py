"""Fetch-side batching (fetch_min_bytes / fetch_max_wait_s): consumers
linger like producers do.  Pins:

- the defaults (and any cfg with ``fetch_max_wait_s=0``) are
  event-stream-identical to the pre-feature broker — the hold branch
  must never be entered;
- with lingering enabled, responses accumulate to ``fetch_min_bytes``
  (fewer, larger batches), no record is lost, and delivery is delayed
  at most ~``fetch_max_wait_s``.
"""
import pytest

from _pins import digest, events_digest, metrics_digest
from repro.core import Engine, PipelineSpec

HORIZON = 20.0
TOTAL = 80
MSG = 512


def spec_with(broker_cfg, delivery="wakeup"):
    spec = PipelineSpec(delivery=delivery)
    spec.add_switch("s1")
    for h in ["b", "p", "c"]:
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=1000.0)
    spec.add_broker("b", **broker_cfg)
    spec.add_topic("t", leader="b")
    # one 512 B record every ~100 ms
    spec.add_producer("p", "SYNTHETIC", topics=["t"], rateKbps=40.0,
                      msgSize=MSG, totalMessages=TOTAL)
    spec.add_consumer("c", "COUNTING", topics=["t"], pollInterval=0.1)
    return spec


def run(broker_cfg, delivery="wakeup", seed=11):
    eng = Engine(spec_with(broker_cfg, delivery), seed=seed)
    mon = eng.run(until=HORIZON)
    sink = [rt for rt in eng.runtimes if rt.name.startswith("consumer")][0]
    return eng, mon, sink


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_max_wait_zero_is_event_stream_identical(delivery):
    # a huge min_bytes with max_wait=0 must be bit-identical to the
    # defaults: the linger feature is gated on BOTH knobs
    base_eng, base_mon, base_sink = run({}, delivery)
    off_eng, off_mon, off_sink = run(
        {"fetch_min_bytes": 1 << 20, "fetch_max_wait_s": 0.0}, delivery)
    assert base_eng.metrics() == off_eng.metrics()
    assert [(e["kind"], e["t"]) for e in base_mon.events] == \
        [(e["kind"], e["t"]) for e in off_mon.events]
    assert base_sink.series == off_sink.series
    assert base_sink.n_received == TOTAL


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_lingering_accumulates_bigger_batches(delivery):
    base_eng, _, base_sink = run({}, delivery)
    lin_eng, _, lin_sink = run(
        {"fetch_min_bytes": 4 * MSG, "fetch_max_wait_s": 1.0}, delivery)
    # every record still arrives...
    assert lin_sink.n_received == base_sink.n_received == TOTAL
    # ...in far fewer, larger response batches (series has one entry
    # per delivered batch)
    assert len(lin_sink.series) < len(base_sink.series)
    assert len(lin_sink.series) <= len(base_sink.series) / 2
    # and the hold is bounded: worst-case extra delay ~ fetch_max_wait_s
    base_lat = max(t for _, t in base_eng.monitor.latencies(topic="t"))
    lin_lat = max(t for _, t in lin_eng.monitor.latencies(topic="t"))
    assert lin_lat <= base_lat + 1.0 + 0.5


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
@pytest.mark.parametrize("seed", [0, 7, 11, 23])
def test_sub_min_bytes_tail_always_delivers(delivery, seed):
    # regression: the expiry re-check must compare against the stored
    # deadline — re-deriving `now - held < max_wait` loses to float
    # rounding and re-parks the waiter with no timer left, stranding
    # the final sub-min-bytes tail forever once producers finish
    eng, _, sink = run(
        {"fetch_min_bytes": 8 * MSG, "fetch_max_wait_s": 0.1},
        delivery, seed=seed)
    assert sink.n_received == TOTAL, \
        f"held tail stranded: {sink.n_received}/{TOTAL} delivered"


# (metrics, monitor event stream, sink series) digests, recorded at
# commit 1e45a58 (tests/_pins.py)
HOLD_PINS = {
    "wakeup": ("59a2549d944468a6", "4f53cda18c2baa0c", "cc2fa78822d25e4d"),
    "poll": ("c569e08a7c9afc8b", "4f53cda18c2baa0c", "3f7b6a272452485d"),
}


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_hold_and_expiry_stream_identical_across_fetch_modes(delivery):
    # `_avail_bytes` reads the cum_list prefix-sum mirror and the
    # hold/expiry decisions run inside the fetch cycle: the full monitor
    # event stream (hold entries, expiry wakeups, delivery ordering) and
    # every metric, event-loop counters included, must match the
    # recorded run; this pipeline has one partition and one subscriber,
    # so no cohorts form
    cfg = {"fetch_min_bytes": 8 * MSG, "fetch_max_wait_s": 0.1}
    eng, mon, sink = run(cfg, delivery)
    assert (metrics_digest(eng.metrics()), events_digest(mon),
            digest(sink.series)) == HOLD_PINS[delivery]
    assert sink.n_received == TOTAL


def test_lingering_wakeup_reduces_engine_events():
    base_eng, _, base_sink = run({}, "wakeup")
    lin_eng, _, lin_sink = run(
        {"fetch_min_bytes": 4 * MSG, "fetch_max_wait_s": 1.0}, "wakeup")
    assert lin_sink.n_received == base_sink.n_received == TOTAL
    # fewer response deliveries -> fewer events on the wakeup hot path
    assert lin_eng.metrics()["engine_events"] < \
        base_eng.metrics()["engine_events"]
