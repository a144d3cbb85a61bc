"""Observability layer: determinism, inertness, boundedness, export.

The telemetry contract (ROADMAP):

- **off (default)**: zero added engine events, zero RNG draws, zero new
  metrics keys — pinned in test_metrics_pin.py; here we pin the
  stronger statement that turning telemetry *on* changes nothing about
  the simulation except the sampler's own events.
- **on**: every artifact (series rings, stage histograms, flight-event
  and profiler call counts, exported traces) is bit-identical for a
  fixed (spec, seed) across processes, both phases of the event queue
  and the columnar axis; produce-side spans additionally agree across
  delivery modes.
- **bounded**: histograms are fixed-bin, series are rings with exact
  running aggregates — memory is O(1) in run length.
"""
import json

import numpy as np
import pytest

from _pins import queue_phase
from repro.core import Engine
from repro.core.telemetry import LatencyHistogram, N_BINS, Series
from repro.obs.trace import chrome_trace, validate_chrome_trace
from repro.sweep import SweepSpec, run_sweep
from repro.sweep.results import TIMING_KEYS
from repro.sweep.scenarios import build_scenario

# the chaos smoke base (benchmarks/sweep_smoke.py) + every telemetry
# surface switched on: bounded queues (queue series + bp flight events),
# an explicit group (lag series), chaos (fault flight events), lineage
BASE = {
    "topology": "geo_wan", "n_hosts": 8, "n_brokers": 3,
    "replication": 3, "n_topics": 2, "n_producers": 2,
    "rate_kbps": 256.0, "msg_size": 512, "consumer_cost": 0.02,
    "queue_bytes": 16 << 10, "consumer_groups": 1, "chaos": 1,
    "horizon": 6.0, "seed": 0,
    "telemetry": 0.5, "profile": 1, "lineage_k": 3,
}

TEL_KEYS = ("telemetry_samples", "telemetry_series", "telemetry_digest",
            "stage_spans", "stage_digest", "lineage_records",
            "flight_events")


def run_one(**over):
    p = {**BASE, **over}
    eng = Engine(build_scenario(p), seed=int(p["seed"]))
    return eng, eng.run_metrics(until=float(p["horizon"]))


# ---------------------------------------------------------------------------
# Bounded primitives
# ---------------------------------------------------------------------------


def test_histogram_add_matches_add_many():
    vals = [0.0, 1e-7, 1e-3, 0.5, 2.0, 999.0, 5e3]
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in vals:
        a.add(v)
    b.add_many(vals)
    assert np.array_equal(a.counts, b.counts)
    assert a.n == b.n == len(vals)
    assert a.sum == pytest.approx(b.sum)
    assert a.counts.size == N_BINS          # fixed allocation, no growth


def test_histogram_quantiles_are_bin_resolution():
    h = LatencyHistogram()
    h.add_many([0.01] * 99 + [0.5])
    # geometric bin midpoint: within one bin width (10^(1/16) ≈ 7%)
    assert h.quantile(0.5) == pytest.approx(0.01, rel=0.08)
    assert h.quantile(0.99) == pytest.approx(0.01, rel=0.08)
    assert h.quantile(1.0) == pytest.approx(0.5, rel=0.08)
    assert h.mean == pytest.approx((0.01 * 99 + 0.5) / 100)
    empty = LatencyHistogram()
    assert empty.quantile(0.5) == 0.0 and empty.mean == 0.0


def test_series_ring_wraps_with_exact_aggregates():
    s = Series(4)
    for v in [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]:
        s.push(v)
    assert list(s.ring()) == [3.0, 4.0, 5.0, 6.0]   # oldest first
    summ = s.summary(0.5)
    assert summ["n"] == 6
    assert summ["mean"] == pytest.approx(3.5)       # over ALL samples
    assert summ["peak"] == 6.0
    assert summ["area"] == pytest.approx(21.0 * 0.5)


# ---------------------------------------------------------------------------
# Telemetry observes, never perturbs
# ---------------------------------------------------------------------------


def test_telemetry_on_only_adds_its_own_sample_events():
    _, off = run_one(telemetry=0.0, profile=0, lineage_k=0)
    _, on = run_one()
    # the sampler is the only event source telemetry adds: executed
    # events grow by exactly the sample count, scheduled events by the
    # sample chain (one pending re-schedule may die past the horizon)
    assert on["engine_events"] == \
        off["engine_events"] + on["telemetry_samples"]
    assert on["telemetry_samples"] > 0
    # everything else — deliveries, chaos faults, shed/pause counters,
    # latency histograms, RNG-dependent outcomes — is bit-identical:
    # telemetry reads state, it never changes it
    skip = {"engine_events", "events_scheduled", "wall_s"}
    for k, want in off.items():
        if k in skip:
            continue
        assert on[k] == want, k


def test_invalid_telemetry_cfg_is_rejected():
    spec = build_scenario({**BASE, "chaos": 0})
    spec.set_telemetry(interval_s=0.0)
    with pytest.raises(ValueError, match="interval_s"):
        Engine(spec, seed=0)


# ---------------------------------------------------------------------------
# Determinism across processes / queue phases / columnar / delivery modes
# ---------------------------------------------------------------------------

FP_GRID = SweepSpec(
    name="telemetry_fp",
    axes={"columnar": [1, 0]},
    base=BASE)


def test_telemetry_fingerprint_stable_across_processes(tmp_path):
    inline = run_sweep(FP_GRID, workers=1, cache_dir=None)
    spawned = run_sweep(FP_GRID, workers=2,
                        cache_dir=str(tmp_path / "cache"))
    assert inline.fingerprint() == spawned.fingerprint()
    for r in inline.rows:
        for k in TEL_KEYS + ("profile_counts",):
            assert k in r["metrics"], k


def test_telemetry_identical_across_scheduler_and_columnar(monkeypatch):
    _, cal = run_one()
    _, rec = run_one(columnar=0)
    queue_phase(monkeypatch, "wheel")
    _, wheel = run_one()
    for k in TEL_KEYS + ("profile_counts",):
        assert cal[k] == wheel[k], k
        assert cal[k] == rec[k], k
    # and the full metric surface matches up to the allocation counter
    # (columnar) / wall clock, same as the PR 5 parity contract
    skip = set(TIMING_KEYS) | {"record_objects_materialized"}
    assert {k: v for k, v in cal.items() if k not in skip} == \
        {k: v for k, v in rec.items() if k not in skip}


def test_produce_side_spans_agree_across_delivery_modes():
    # poll and wakeup deliver at different times by design (the latency
    # pins differ per mode), but the produce→append→replicate side is
    # delivery-independent: identical span histograms on both modes
    _, wk = run_one(delivery="wakeup")
    _, pl = run_one(delivery="poll")
    for stage in ("append", "replicate"):
        keys = [k for k in wk["stage_spans"] if k.startswith(stage)]
        assert keys, stage
        for k in keys:
            assert wk["stage_spans"][k] == pl["stage_spans"][k], k


def test_repeat_run_is_bit_identical_including_digests():
    _, a = run_one()
    _, b = run_one()
    skip = set(TIMING_KEYS)
    assert {k: v for k, v in a.items() if k not in skip} == \
        {k: v for k, v in b.items() if k not in skip}


# ---------------------------------------------------------------------------
# Series / span / profiler content
# ---------------------------------------------------------------------------


def test_series_cover_rates_lag_queue_and_isr():
    _, m = run_one()
    names = set(m["telemetry_series"])
    for prefix in ("bytes_s:", "recs_s:", "isr:", "lag:", "queue:",
                   "paused:"):
        assert any(n.startswith(prefix) for n in names), prefix
    # delivered bytes showed up as a positive rate somewhere
    assert any(s["peak"] > 0 for n, s in m["telemetry_series"].items()
               if n.startswith("bytes_s:"))
    assert m["telemetry_samples"] >= 10        # 6 s / 0.5 s cadence


def test_watermark_lag_series_present_for_event_time_spe():
    _, m = run_one(windowed=1, window_s=1.0, et_jitter_s=0.3,
                   chaos=0, queue_bytes=0)
    assert any(n.startswith("wmlag:") for n in m["telemetry_series"])


def test_stage_spans_cover_the_pipeline():
    _, m = run_one()
    stages = {k.split(":", 1)[0] for k in m["stage_spans"]}
    assert {"append", "replicate", "fetch", "deliver",
            "sink"} <= stages
    for k, s in m["stage_spans"].items():
        assert s["count"] > 0 or not s["count"]
        assert s["p50"] <= s["p99"]
    # first-delivery latency histogram backs the top-level metrics
    assert m["latency_count"] == m["records_delivered"]


def test_profiler_counts_fingerprinted_wall_excluded():
    assert "profile_wall" in TIMING_KEYS
    eng, m = run_one()
    counts, wall = m["profile_counts"], m["profile_wall"]
    assert counts["scheduler_pops"] == m["engine_events"]
    assert counts["netem_path"] == m["path_queries"]
    # PR 9 split the old whole-call "fetch" bucket: fetch_ctl counts
    # per-partition control attempts, fetch_take counts partitions that
    # passed control and tried to take rows; "deliver" stays per-view in
    # both fetch modes, "deliver_cohort" counts fused cohort events
    assert counts["fetch_ctl"] > 0 and counts["fetch_take"] > 0
    assert counts["deliver"] > 0
    assert counts["deliver_cohort"] > 0
    assert counts["deliver"] >= counts["deliver_cohort"]
    assert all(isinstance(v, int) for v in counts.values())
    assert all(isinstance(v, float) for v in wall.values())
    assert {"scheduler_pop", "event_fn", "netem_path"} <= set(wall)


def test_lineage_traces_follow_stage_order():
    eng, m = run_one()
    traces = eng.telemetry.lineage_traces()
    assert 0 < len(traces) == m["lineage_records"] <= 3 * 2  # k * topics
    for tr in traces:
        stages = [s for s, _ in tr["stages"]]
        times = [t for _, t in tr["stages"]]
        assert stages[0] == "produce"
        assert times == sorted(times)          # marks move forward
    # at least one traced record made it end to end
    assert any("deliver" in [s for s, _ in tr["stages"]]
               for tr in traces)


# ---------------------------------------------------------------------------
# Trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_is_valid_and_deterministic(tmp_path):
    eng_a, _ = run_one()
    eng_b, _ = run_one()
    obj = chrome_trace(eng_a)
    assert validate_chrome_trace(obj) == []
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert {"M", "i", "C", "X"} <= phases
    # byte-identical across runs: traces are fingerprintable artifacts
    assert json.dumps(obj, sort_keys=True) == \
        json.dumps(chrome_trace(eng_b), sort_keys=True)
    out = tmp_path / "run.json"
    eng_a.export_trace(str(out))
    assert validate_chrome_trace(json.loads(out.read_text())) == []


def test_trace_export_requires_telemetry():
    eng, _ = run_one(telemetry=0.0, profile=0, lineage_k=0)
    with pytest.raises(RuntimeError, match="telemetry"):
        chrome_trace(eng)


def test_validator_flags_schema_violations():
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1},                 # bad phase
        {"ph": "i", "pid": 1, "ts": 1.0},                   # no name
        {"ph": "X", "name": "s", "pid": 1, "ts": 1.0},      # no dur
        {"ph": "C", "name": "c", "pid": 1, "ts": 1.0,
         "args": {"value": "NaN-string"}},                  # non-numeric
    ]}
    problems = validate_chrome_trace(bad)
    assert len(problems) == 4
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({}) != []
