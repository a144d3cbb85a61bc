"""Chaos fault plans, overlap-safe fault application, backpressure and
load shedding (ISSUE 6 acceptance):

- overlapping faults on one target stack instead of clobbering — the
  gray-loss heal regression (healing an *earlier* fault restored its
  stale captured baseline over a still-active later fault) is pinned;
- spec validation rejects malformed faults and chaos plans at
  ``Engine`` construction instead of a mid-run netem ``KeyError``;
- one (spec, seed) names an entire adversarial run bit-identically:
  the expanded schedule and every degradation counter reproduce across
  processes-in-spirit (fresh engines), delivery modes and queue phases;
- bounded ingest queues hold their byte bound under overload: ``pause``
  throttles the fetch path (and resumes — no hung waiters), shed
  policies drop deterministically at admission with counted metrics;
- ``exactly_once`` + checkpointing under a chaos plan with a bounded
  (pause) SPE queue still emits exactly the fault-free reference.
"""
import pytest

from _pins import queue_phase
from repro.core import Engine, PipelineSpec
from repro.core.faults import expand_chaos
from repro.core.operators import shed_keep


def star_spec(delivery="wakeup", **consumer_cfg):
    spec = PipelineSpec(delivery=delivery)
    spec.add_switch("s1")
    for h in ("b", "p", "c"):
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=1000.0)
    spec.add_broker("b")
    spec.add_topic("t", leader="b")
    spec.add_producer("p", "SYNTHETIC", topics=["t"], rateKbps=40.0,
                      msgSize=500, totalMessages=40)
    spec.add_consumer("c", "STANDARD", topics=["t"], pollInterval=0.1,
                      **consumer_cfg)
    return spec


# ---------------------------------------------------------------------------
# Overlap-safe fault stacks (satellite: gray heal regression)
# ---------------------------------------------------------------------------


def probe(eng, times, fn):
    out = {}
    for t in times:
        eng.schedule(t, lambda t=t: out.__setitem__(t, fn()))
    return out


def test_overlapping_gray_loss_restores_active_max_then_baseline():
    spec = star_spec()
    spec.network.link("b", "s1").loss_pct = 1.0       # spec baseline
    spec.add_fault(1.0, "gray_loss", "b", "s1", duration=4.0,
                   loss_pct=30.0)
    spec.add_fault(2.0, "gray_loss", "b", "s1", duration=1.0,
                   loss_pct=50.0)
    eng = Engine(spec, seed=0)
    seen = probe(eng, [1.5, 2.5, 3.5, 6.0],
                 lambda: eng.net.link("b", "s1").loss_pct)
    eng.run(until=8.0)
    assert seen[1.5] == 30.0
    assert seen[2.5] == 50.0          # overlap: max over active faults
    # the regression: healing the 50% fault must fall back to the still-
    # active 30% fault, not to the 30% it captured as "prev" at apply
    # time, and the final heal must restore the 1% spec baseline
    assert seen[3.5] == 30.0
    assert seen[6.0] == 1.0


def test_overlapping_link_down_heals_only_when_last_fault_ends():
    spec = star_spec()
    spec.add_fault(1.0, "link_down", "c", "s1", duration=2.0)
    spec.add_fault(2.0, "link_down", "c", "s1", duration=3.0)
    eng = Engine(spec, seed=0)
    seen = probe(eng, [1.5, 3.5, 5.5],
                 lambda: eng.net.link("c", "s1").up)
    mon = eng.run(until=8.0)
    assert seen[1.5] is False
    assert seen[3.5] is False, "first heal must not revive the link"
    assert seen[5.5] is True
    # depth-counted: two down events, ONE up event (at the last heal)
    assert len(mon.events_of("link_down")) == 2
    assert len(mon.events_of("link_up")) == 1


def test_slow_host_fault_stacks_and_heals():
    spec = star_spec()
    spec.add_fault(1.0, "slow_host", "b", duration=4.0, delay_s=0.05)
    spec.add_fault(2.0, "slow_host", "b", duration=1.0, delay_s=0.2)
    eng = Engine(spec, seed=0)
    seen = probe(eng, [1.5, 2.5, 3.5, 6.0],
                 lambda: eng.net.slow_extra_s.get("b", 0.0))
    mon = eng.run(until=8.0)
    assert seen[1.5] == 0.05
    assert seen[2.5] == 0.2           # overlap: max over active delays
    assert seen[3.5] == 0.05
    assert seen[6.0] == 0.0
    assert len(mon.events_of("slow_host")) == 2
    assert len(mon.events_of("slow_heal")) == 1


def test_slow_host_delays_transfers_end_to_end():
    def p99(spec):
        eng = Engine(spec, seed=0)
        eng.run(until=10.0)
        return eng.metrics()["latency_p99"]

    slow = star_spec()
    slow.add_fault(0.0, "slow_host", "b", delay_s=0.25)  # permanent
    assert p99(slow) > p99(star_spec()) + 0.2


# ---------------------------------------------------------------------------
# Spec validation (satellite: fail fast, not a mid-run KeyError)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault,needle", [
    (dict(kind="link_down", target=("b", "nope")), "unknown"),
    (dict(kind="link_down", target=("b", "c")), "no link"),
    (dict(kind="link_down", target=("b",)), "needs (a, b)"),
    (dict(kind="host_down", target=("b", "c")), "one host"),
    (dict(kind="host_down", target=("ghost",)), "unknown"),
    (dict(kind="vaporize", target=("b",)), "unknown kind"),
    (dict(kind="gray_loss", target=("b", "s1"), loss_pct=140.0),
     "loss_pct"),
    (dict(kind="slow_host", target=("b",), delay_s=-1.0), "delay"),
])
def test_fault_validation_fails_fast(fault, needle):
    spec = star_spec()
    spec.faults.append(
        __import__("repro.core.spec", fromlist=["FaultCfg"]).FaultCfg(
            at=1.0, duration=1.0, **fault))
    problems = spec.validate()
    assert any(needle in p for p in problems), (needle, problems)
    with pytest.raises(ValueError):
        Engine(spec, seed=0)


@pytest.mark.parametrize("chaos,needle", [
    (dict(crashes=1), "duration"),
    (dict(duration=5.0, crashes=-1), "counts must be >= 0"),
    (dict(duration=5.0, flap_links=1, flap_duty=1.5), "duty"),
    (dict(duration=5.0, gray=1, gray_max_loss_pct=200.0), "loss"),
    (dict(duration=5.0, gray=1, gray_steps=0), "steps"),
    (dict(duration=5.0, crashes=1, protect=("ghost",)), "unknown"),
    (dict(duration=5.0, crashes=1, protect=("b", "p", "c")),
     "unprotected"),
])
def test_chaos_validation_fails_fast(chaos, needle):
    spec = star_spec()
    spec.set_chaos(**chaos)
    problems = spec.validate()
    assert any(needle in p for p in problems), (needle, problems)
    with pytest.raises(ValueError):
        Engine(spec, seed=0)


# ---------------------------------------------------------------------------
# Chaos plans: seeded, deterministic, mode-blind
# ---------------------------------------------------------------------------


def chaos_spec(delivery="wakeup", seed_axis=0):
    spec = star_spec(delivery=delivery)
    spec.set_chaos(start=1.0, duration=6.0, flap_links=1 + seed_axis,
                   gray=1, slow=1, crashes=1, protect=("b", "p"))
    return spec


def test_chaos_expansion_is_bit_identical_for_one_seed():
    spec = chaos_spec()
    eng = Engine(spec, seed=5)
    a = expand_chaos(spec, spec.chaos, eng.client_rng("chaos"))
    b = expand_chaos(spec, spec.chaos,
                     Engine(chaos_spec(), seed=5).client_rng("chaos"))
    assert a and a == b
    c = expand_chaos(spec, spec.chaos,
                     Engine(chaos_spec(), seed=6).client_rng("chaos"))
    assert a != c, "a different seed must draw a different plan"


def test_chaos_crashes_respect_protect():
    spec = chaos_spec()
    eng = Engine(spec, seed=5)
    plan = expand_chaos(spec, spec.chaos, eng.client_rng("chaos"))
    crash_hosts = {f.target[0] for f in plan
                   if f.kind in ("host_down", "slow_host")}
    assert crash_hosts == {"c"}, "only the unprotected host may crash"


def fault_trace(mon):
    return [(e["t"], k, tuple(sorted(e.items())))
            for k in ("link_down", "link_up", "gray_loss", "gray_heal",
                      "slow_host", "slow_heal", "host_down", "host_up")
            for e in mon.events_of(k)]


@pytest.mark.parametrize("axis", [
    {"delivery": "poll"}, {"phase": "wheel"}])
def test_chaos_schedule_blind_to_delivery_mode_and_scheduler(axis,
                                                            monkeypatch):
    ref_eng = Engine(chaos_spec(), seed=5)
    ref = ref_eng.run(until=10.0)
    queue_phase(monkeypatch, axis.get("phase", "calendar"))
    eng = Engine(chaos_spec(axis.get("delivery", "wakeup")), seed=5)
    mon = eng.run(until=10.0)
    assert fault_trace(mon) == fault_trace(ref)
    a, b = eng.metrics(), ref_eng.metrics()
    for k in ("chaos_faults", "fault_events", "produce_retries",
              "produce_expired", "records_produced"):
        assert a[k] == b[k], k


# ---------------------------------------------------------------------------
# Backpressure: bounded queues pause + resume; shed policies drop
# ---------------------------------------------------------------------------

BOUND = 4096


def overload_spec(delivery, policy, bound=BOUND):
    # 500-byte records at ~10/s against a 250 ms/record consumer: the
    # bounded queue must fill and the policy must act
    return star_spec(delivery=delivery, queueBytes=bound,
                     shedPolicy=policy, perRecordCost=0.25)


def spe_overload_spec(delivery, policy, bound=BOUND):
    """The shape where pauses actually occur: SPE runtimes set no busy
    gate (their service time queues on the host compute model), so the
    fetch loop keeps delivering into the bounded queue while a starved
    single-core host works the backlog off."""
    spec = PipelineSpec(delivery=delivery)
    spec.add_switch("s1")
    for h in ("b", "p", "c"):
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=1000.0)
    spec.add_host("w", n_cores=1, cpu_percentage=0.04)  # 2500x scale
    spec.add_link("w", "s1", lat=1.0, bw=1000.0)
    spec.add_broker("b")
    spec.add_topic("in", leader="b")
    spec.add_topic("agg", leader="b")
    spec.add_producer("p", "SYNTHETIC", topics=["in"], rateKbps=40.0,
                      msgSize=500, totalMessages=40)
    spec.add_spe("w", query="identity", inTopic="in", outTopic="agg",
                 pollInterval=0.1, queueBytes=bound, shedPolicy=policy)
    spec.add_consumer("c", "STANDARD", topic="agg", pollInterval=0.1)
    return spec


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_consumer_pause_budget_caps_fetch_and_drains(delivery):
    # consumer stubs busy-gate their own fetches, so the bound shows up
    # as a fetch-size cap: the queue never exceeds it and nothing drops
    eng = Engine(overload_spec(delivery, "pause"), seed=2)
    eng.run(until=60.0)
    sub = [rt for rt in eng.runtimes if rt.name.startswith("consumer")][0]
    m = eng.metrics()
    assert 0 < m["queue_peak_bytes"] <= BOUND
    assert sub._q_peak <= BOUND
    assert m["records_shed"] == 0, "pause must never drop records"
    # no hung waiter: once the producer stops, the loop still drains
    # the whole backlog to the subscriber
    assert sub.n_received == 40
    assert m["records_delivered"] == 40


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_spe_pause_throttles_resumes_and_loses_nothing(delivery):
    eng = Engine(spe_overload_spec(delivery, "pause"), seed=2)
    eng.run(until=120.0)
    spe = [rt for rt in eng.runtimes if rt.name.startswith("spe")][0]
    m = eng.metrics()
    assert 0 < m["queue_peak_bytes"] <= BOUND
    assert m["backpressure_pauses"] > 0 and m["pause_seconds"] > 0
    assert m["records_shed"] == 0, "pause must never drop records"
    # paused loops resumed on every drain: the full input was processed
    assert spe.n_processed == 40


@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_spe_shed_policy_drops_under_overload(delivery):
    eng = Engine(spe_overload_spec(delivery, "drop_oldest"), seed=2)
    eng.run(until=120.0)
    spe = [rt for rt in eng.runtimes if rt.name.startswith("spe")][0]
    m = eng.metrics()
    assert spe._q_peak <= BOUND
    assert m["records_shed"] > 0
    assert spe.n_processed + spe.n_shed == 40


def test_single_record_larger_than_bound_does_not_deadlock():
    eng = Engine(overload_spec("wakeup", "pause", bound=100), seed=2)
    eng.run(until=60.0)
    sub = [rt for rt in eng.runtimes if rt.name.startswith("consumer")][0]
    # the escape hatch: a record bigger than the whole bound is taken
    # anyway (documented overshoot) instead of wedging the loop forever
    assert sub.n_received == 40


@pytest.mark.parametrize("policy", ["drop_oldest", "drop_newest",
                                    "sample"])
@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_shed_policies_bound_queue_and_count_drops(delivery, policy):
    eng = Engine(overload_spec(delivery, policy), seed=2)
    eng.run(until=20.0)
    sub = [rt for rt in eng.runtimes if rt.name.startswith("consumer")][0]
    m = eng.metrics()
    assert sub._q_peak <= BOUND
    assert m["records_shed"] > 0 and m["bytes_shed"] > 0
    assert m["records_shed"] == sub.n_shed
    # every fetched row is either processed or counted shed — never both
    assert sub.n_received + sub.n_shed == m["records_delivered"]
    assert len(eng.monitor.events_of("records_shed")) > 0


@pytest.mark.parametrize("policy", ["drop_oldest", "sample"])
def test_shed_counts_are_deterministic(policy):
    def counters():
        eng = Engine(overload_spec("wakeup", policy), seed=2)
        eng.run(until=20.0)
        m = eng.metrics()
        return (m["records_shed"], m["bytes_shed"],
                m["queue_peak_bytes"], m["engine_events"])

    assert counters() == counters()


def test_shed_keep_is_pure_and_bounded():
    sizes = [100, 300, 200, 50, 400]
    for policy in ("drop_oldest", "drop_newest", "sample"):
        how, sel, kept = shed_keep(sizes, 500, policy)
        assert kept <= 500
        if how == "slice":
            lo, hi = sel
            assert kept == sum(sizes[lo:hi])
        else:
            assert kept == sum(sizes[i] for i in sel)
            assert sel == sorted(sel)
    # drop_newest keeps the longest fitting prefix (100+300=400),
    # drop_oldest the longest fitting suffix (50+400=450)
    assert shed_keep(sizes, 500, "drop_newest")[1] == (0, 2)
    assert shed_keep(sizes, 500, "drop_oldest")[1] == (3, 5)
    assert shed_keep(sizes, 0, "drop_oldest") == ("slice", (5, 5), 0)
    with pytest.raises(ValueError):
        shed_keep(sizes, 500, "roulette")


# ---------------------------------------------------------------------------
# Acceptance: chaos + overload + bounded queue, exactly_once intact
# ---------------------------------------------------------------------------


def windowed_spec(*, chaos, bound):
    spec = PipelineSpec(delivery="wakeup")
    spec.add_switch("s1")
    for h in ("b", "p1", "w", "c"):
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=1000.0)
    spec.add_broker("b")
    spec.add_topic("in", leader="b", partitions=2)
    spec.add_topic("agg", leader="b")
    spec.add_producer("p1", "SYNTHETIC", topics=["in"], rateKbps=40.0,
                      msgSize=500, totalMessages=60, etJitterS=0.3)
    cfg = dict(query="identity", inTopic="in", outTopic="agg",
               timeMode="event", window=1.0, allowedLateness=0.2,
               keyField="src", agg="count", checkpointInterval=0.5,
               semantics="exactly_once", pollInterval=0.1)
    if bound:
        cfg.update(queueBytes=bound, shedPolicy="pause")
    spec.add_spe("w", **cfg)
    spec.add_consumer("c", "METRICS", topic="agg", pollInterval=0.1)
    if chaos:
        # the crash/heal cycles can only land on the SPE host — the
        # adversarial schedule is seeded, the outcome must not be
        spec.set_chaos(start=3.0, duration=10.0, crashes=2,
                       crash_downtime_s=2.0, protect=("b", "p1", "c"))
    return spec


def window_multiset(eng):
    sink = [rt for rt in eng.runtimes if rt.name.startswith("consumer")][0]
    return sorted((repr(p["key"]), tuple(p["window"]), p["value"],
                   p["n"]) for p in sink.payloads)


def test_exactly_once_under_chaos_with_bounded_queue():
    ref = Engine(windowed_spec(chaos=False, bound=0), seed=3)
    ref.run(until=40.0)
    reference = window_multiset(ref)
    assert reference, "reference run must fire windows"

    eng = Engine(windowed_spec(chaos=True, bound=2048), seed=3)
    eng.run(until=40.0)
    m = eng.metrics()
    assert m["chaos_faults"] == 2
    assert m["spe_recoveries"] >= 1, "a chaos crash must actually land"
    assert m["recovered_duplicates"] == 0
    assert m["records_shed"] == 0
    assert m["queue_peak_bytes"] <= 2048
    assert window_multiset(eng) == reference, \
        "chaos + bounded pause queue must not change exactly_once output"
