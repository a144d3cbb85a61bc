"""A whole run on the CPU, chip look skipped, with the timed path broken
underneath: each fault the cells can have turns ``correct`` false."""
import pytest

from bench import catalog, run

CELLS = ["trafficmon_100u", "kreps2014_rep3"]


def args(cell="trafficmon_100u"):
    return ["--workload", cell, "--seed", "4000000007", "--seconds", "2",
            "--trace", "0"]


@pytest.fixture
def cpu(monkeypatch):
    """The run's device look, answered by the CPU."""
    import jax
    monkeypatch.setattr(run, "devices", lambda chips: (jax, jax.devices()))


def go(cell="trafficmon_100u"):
    res, _ = run.run(args(cell))
    return res


def test_sound_run_is_correct_and_reports_every_number(cpu):
    res = go()
    assert res["correct"] is True and res["failed"] == 0
    cmp = res["compared"]
    assert cmp["stage_gap"]["value"] == 0.0
    assert cmp["stage_windows"]["value"] >= 2
    assert cmp["deliveries_checked"]["value"] > 1000
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"sim_rate", "setup_s"}
    assert res["runinfo"]["window_compiles"] == 0


def test_kafka_cell_is_correct_on_every_topic(cpu):
    res = go("kreps2014_rep3")
    assert res["correct"] is True
    assert res["compared"]["stage_windows"]["value"] >= 2


def altered(run_):
    def f(self, spe, eng, records):
        out = run_(self, spe, eng, records)
        out[0][0]["connections"]["web"] += 1.0
        return out
    return f


def stale(run_):
    first = []

    def f(self, spe, eng, records):
        out = run_(self, spe, eng, records)
        if not first:
            first.append(out)
        return first[0]
    return f


def half_window(run_):
    def f(self, spe, eng, records):
        return run_(self, spe, eng, records[: len(records) // 2])
    return f


def bf16_control(run_):
    return catalog.stage("traffic_metrics").bf16_run


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [altered, stale, half_window, bf16_control],
                         ids=["answer_altered", "state_unchanged",
                              "half_window", "bf16_control"])
def test_broken_stage_is_not_correct(cpu, monkeypatch, fault, cell):
    from repro.core.spe import TrafficMetricsQuery

    monkeypatch.setattr(TrafficMetricsQuery, "_run",
                        fault(TrafficMetricsQuery._run))
    res = go(cell)
    assert res["correct"] is False
    assert res["compared"]["stage_gap"]["value"] > 0


def test_windowing_shifted_in_the_program_is_not_correct(cpu, monkeypatch):
    # the stream processor's windows close at 1.5, 2.5, ... where its
    # deployment says 1, 2, ...
    from repro.core.spe import SPERuntime

    orig = SPERuntime.flush

    def late_flush(self, eng):
        if not getattr(self, "_shifted", False):
            self._shifted = True
            eng.schedule(0.5, lambda: orig(self, eng))
            return
        orig(self, eng)

    monkeypatch.setattr(SPERuntime, "flush", late_flush)
    res = go()
    assert res["correct"] is False
    assert res["compared"]["stage_gap"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_each_delivered_batch_left_out(cpu, monkeypatch, cell):
    from repro.core import broker

    orig = broker.Cluster._deliver_cohort

    def halved(self, consumer, topic, group):
        group = [(t, b.subview(0, len(b) // 2) if len(b) > 1 else b, m, p)
                 for t, b, m, p in group]
        return orig(self, consumer, topic, group)

    monkeypatch.setattr(broker.Cluster, "_deliver_cohort", halved)
    res = go(cell)
    assert res["correct"] is False
    assert res["compared"]["undelivered"]["value"] > 0
