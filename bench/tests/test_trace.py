"""Trace reduction: busy union, idle gaps, top ops, gap labels."""
import pytest

from bench import trace as tr


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_union_refuses_reversed_interval():
    with pytest.raises(ValueError):
        tr.union([(2, 1)])


def test_busy_counts_overlap_once_and_clips_to_window():
    ops = [(0, 10), (5, 15), (20, 30), (40, 50)]
    assert tr.busy(ops, 0, 100) == 15 + 10 + 10
    assert tr.busy(ops, 8, 25) == (15 - 8) + (25 - 20)
    assert tr.busy([], 0, 10) == 0


def test_gaps_cover_window_minus_busy():
    ops = [(10, 20), (15, 30), (50, 60)]
    g = tr.gaps(ops, 0, 100)
    assert g == [(0, 10), (30, 50), (60, 100)]
    assert sum(e - s for s, e in g) + tr.busy(ops, 0, 100) == 100
    assert tr.gaps([], 0, 5) == [(0, 5)]
    assert tr.gaps([(0, 5)], 0, 5) == []


def test_top_by_name_sums_durations_in_seconds():
    ev = [("fusion", 0, 1_000_000), ("sort", 0, 4_000_000),
          ("fusion", 5, 2_000_005)]
    (name, sec), = tr.top_by_name(ev, k=1)
    assert name == "sort" and sec == pytest.approx(4e-3)
    got = dict(tr.top_by_name(ev))
    assert got["fusion"] == pytest.approx(3e-3)


def test_label_gaps_by_most_covering_bucket():
    gaps = [(0, 100), (200, 210), (300, 400)]
    host = {"fetch_take": ([0], [70]), "netem_path": ([70, 300], [90, 320])}
    out = tr.label_gaps(gaps, host, k=2)
    # the two longest gaps, longest first
    assert [n for n, _ in out] == ["fetch_take", "unattributed"]
    assert [s for _, s in out] == pytest.approx([100e-9, 100e-9])


def test_overlap_clips_spans_to_the_gap():
    assert tr.overlap([0, 10, 30], [5, 20, 40], 3, 35) == 2 + 10 + 5
    assert tr.overlap([], [], 0, 10) == 0.0


def test_reduce_maps_host_clock_and_attributes_stage_time():
    import numpy as np
    # trace clock = perf ns + 1000; window from the mark (trace 1000) to
    # perf 0.001 s (trace 1_001_000)
    got = {"host": {"sync": [(1000, 1001)]},
           "devices": {"/device:TPU:0": [("fusion", 150_000, 160_000),
                                         ("sort", 650_000, 680_000),
                                         ("fusion", 900_000, 910_000)]}}
    # the stage calls are the operator spans, two inside the window
    spans = {"fetch_take": (np.array([300e-6]), np.array([550e-6])),
             "operator": (np.array([99e-6, 599e-6, 1999e-6]),
                          np.array([199e-6, 699e-6, 2099e-6]))}
    traced, bd = tr.reduce(got, 0, 0.001, spans, 1, "sync")
    assert traced["window_s"] == pytest.approx(1e-3)
    assert traced["busy_s"] == pytest.approx(50e-6)
    assert traced["stage_calls"] == 2
    assert traced["stage_busy_s"] == pytest.approx(50e-6)
    assert bd["device_ops"][0][0] == "sort"
    # longest gap 160_000..650_000 (trace) holds fetch_take 301_000..551_000
    assert bd["idle_gaps"][0] == ["fetch_take", pytest.approx(490e-6)]
    with pytest.raises(RuntimeError):
        tr.reduce({"host": {}, "devices": {}}, 0, 1.0, {}, 1, "sync")
