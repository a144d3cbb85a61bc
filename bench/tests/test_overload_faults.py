"""``kreps2014_overload`` as a whole run on the CPU, chip look skipped:
a sound run is correct and holds the consumer's bound, and each fault of
the timed path turns ``correct`` false.  This is the only cell whose
deliveries carry many records, so cutting each batch in half cuts."""
import pytest

from bench import catalog, run, window

CELL = "kreps2014_overload"


@pytest.fixture
def cpu(monkeypatch):
    """The run's device look, answered by the CPU."""
    import jax
    monkeypatch.setattr(run, "devices", lambda chips: (jax, jax.devices()))


def go():
    res, _ = run.run(["--workload", CELL, "--seed", "4000000011",
                      "--seconds", "3", "--trace", "0"])
    return res


def test_sound_run_is_correct_within_the_consumer_bound(cpu, monkeypatch):
    engines = []

    class Kept(window.Scenario):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            engines.append(self.eng)

    monkeypatch.setattr(window, "Scenario", Kept)
    res = go()
    assert res["correct"] is True and res["failed"] == 0
    assert res["compared"]["deliveries_checked"]["value"] > 1000
    bound = catalog.params(catalog.cell(catalog.load(), CELL))[
        "consumer_queue_bytes"]
    for eng in engines:
        m = eng.metrics()
        assert 0 < m["queue_peak_bytes"] <= bound
        assert m["records_shed"] == 0


def stale(run_):
    first = []

    def f(self, spe, eng, records):
        out = run_(self, spe, eng, records)
        if not first:
            first.append(out)
        return first[0]
    return f


def bf16_control(run_):
    return catalog.stage("traffic_metrics").bf16_run


@pytest.mark.parametrize("fault", [stale, bf16_control],
                         ids=["state_unchanged", "bf16_control"])
def test_broken_stage_is_not_correct(cpu, monkeypatch, fault):
    from repro.core.spe import TrafficMetricsQuery

    monkeypatch.setattr(TrafficMetricsQuery, "_run",
                        fault(TrafficMetricsQuery._run))
    res = go()
    assert res["correct"] is False
    assert res["compared"]["stage_gap"]["value"] > 0


def test_half_of_each_delivered_batch_left_out(cpu, monkeypatch):
    from repro.core import broker

    orig = broker.Cluster._deliver_cohort
    cut = [0]

    def halved(self, consumer, topic, group):
        out = []
        for t, b, m, p in group:
            if len(b) > 1:
                cut[0] += len(b) - len(b) // 2
                b = b.subview(0, len(b) // 2)
            out.append((t, b, m, p))
        return orig(self, consumer, topic, out)

    monkeypatch.setattr(broker.Cluster, "_deliver_cohort", halved)
    res = go()
    assert cut[0] > 100
    assert res["correct"] is False
    assert res["compared"]["undelivered"]["value"] > 0
