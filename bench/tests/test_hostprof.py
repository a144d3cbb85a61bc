"""Self time from nested profiler spans, and ms per emulated second."""
import pytest

from bench.hostprof import Recorder, layer_ms_per_sim_s, merge


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_nested_spans_give_self_time():
    clk = FakeClock()
    r = Recorder(clock=clk)
    # fetch_take [0, 10] holds netem_path [2, 5]; deliver [12, 20] holds
    # operator [13, 18], which holds netem_path [14, 15]
    clk.t = 5.0
    r.add_wall("netem_path", 3.0)
    clk.t = 10.0
    r.add("fetch_take", 10.0)
    clk.t = 15.0
    r.add_wall("netem_path", 1.0)
    clk.t = 18.0
    r.add("operator", 5.0)
    clk.t = 20.0
    r.add("deliver_cohort", 8.0)
    assert r.self_s == pytest.approx({"netem_path": 4.0, "fetch_take": 7.0,
                                      "operator": 4.0,
                                      "deliver_cohort": 3.0})
    # the program's own sums are kept as they are
    assert r.wall["netem_path"] == 4.0 and r.counts["fetch_take"] == 1


def test_build_reported_before_its_path_query_is_a_sibling():
    # netem adds the table build, then the path query net of the build,
    # both when the query returns: the parent loses both, neither the
    # other
    clk = FakeClock()
    r = Recorder(clock=clk)
    clk.t = 10.0
    r.add_wall("netem_build", 6.0)
    r.add_wall("netem_path", 1.0)
    clk.t = 12.0
    r.add("fetch_ctl", 9.0)
    assert r.self_s == pytest.approx({"netem_build": 6.0, "netem_path": 1.0,
                                      "fetch_ctl": 2.0})


def test_totals_and_zero_spans_are_not_intervals():
    clk = FakeClock()
    r = Recorder(clock=clk)
    clk.t = 3.0
    r.add("deliver", 0.0, n=4)
    r.add_wall("event_fn", 3.0)
    r.add_wall("scheduler_pop", 1.0)
    assert r.self_s == {} and r.spans == {}
    assert r.counts["deliver"] == 4


def test_layers_add_up_to_the_profiled_loop():
    clk = FakeClock()
    r = Recorder(clock=clk)
    clk.t = 2.0
    r.add("fetch_take", 2.0)
    clk.t = 3.0
    r.add("operator", 0.5)
    r.add_wall("event_fn", 4.0)
    r.add_wall("scheduler_pop", 1.0)
    tot = merge([r])
    per = layer_ms_per_sim_s(tot, sim_s=10.0)
    assert per["unattributed"] == pytest.approx(1e3 * 1.5 / 10)
    assert per["scheduler_pop"] == pytest.approx(100.0)
    assert sum(v for k, v in per.items() if k != "event_fn") == \
        pytest.approx(1e3 * (4.0 + 1.0) / 10)
    assert layer_ms_per_sim_s(tot, 0.0) == {}


def test_merge_sums_recorders():
    a, b = Recorder(clock=lambda: 1.0), Recorder(clock=lambda: 1.0)
    a.add("operator", 1.0)
    b.add("operator", 0.5)
    b.add_wall("event_fn", 2.0)
    assert merge([a, b]) == {"operator": 1.5, "event_fn": 2.0}


def test_metric_readers_sum_their_buckets():
    from bench import catalog
    run = {"host": {"fetch_ctl": 0.2, "fetch_take": 0.3, "event_fn": 1.0,
                    "scheduler_pop": 0.1},
           "host_sim_s": 10.0, "events": 500.0}
    assert catalog.reader("fetch_ms")(run) == pytest.approx(50.0)
    assert catalog.reader("unattributed_ms")(run) == pytest.approx(50.0)
    assert catalog.reader("sched_ms")(run) == pytest.approx(10.0)
    assert catalog.reader("events_per_sim_s")(run) == pytest.approx(50.0)
    assert catalog.reader("operator_ms")(run) is None
