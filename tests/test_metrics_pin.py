"""Fingerprint-parity regression pins.

PR 3 (partitions): ``partitions=1`` + unkeyed producers + ``linger_ms=0``
must reproduce the pre-partition engine *exactly*: the values below are
``Engine.metrics()`` outputs for the CI sweep-smoke grid captured at the
pre-refactor commit (PR 2 head).  Every pinned field — event counts, RNG-
dependent latencies at full float precision, delivery tallies — must
still match bit-for-bit.  New fields added by the refactor (per-partition
tallies, ``produce_batches``, …) are intentionally not pinned; moved
fields are covered by the compat shims (``TopicMeta`` proxies, string-
keyed ``cluster.logs``).

PR 4 (operator graphs): the processing-time / no-checkpoint SPE
configuration must reproduce the pre-operator-graph runtime *exactly* —
the word-count pipeline pins below (engine events + a digest of the
sink's payload sequence) were captured at the PR 3 head, before
``core/spe.py`` was refactored from monolithic ``Query`` subclasses
into operator chains.

PR 5 (allocation-free delivery): the defaults are now columnar
``BatchView`` delivery on the calendar-queue scheduler — the original
pin grids run under those defaults, so PINNED passing at all *is* the
bit-for-bit proof for the new hot path.  The additional sections pin
the compat configuration ``columnar=False`` (per-row Record
materialization, the pre-BatchView delivery pattern) against the same
numbers, with only ``record_objects_materialized`` allowed to differ,
and rerun the grid with the calendar queue on its bucketed wheel from
the first push: its rows must equal the default (heap-phase) rows.

PR 7 (telemetry): ``latency_mean``/``latency_p50``/``latency_p99`` were
re-pinned (full precision, captured at the PR 6 head + histogram change
only).  The unbounded per-delivery latency list was replaced by the
monitor's bounded log-spaced histogram: the mean now accumulates in
delivery order (last-ulp difference vs the old produce-order
``np.mean``) and p50/p99 are geometric bin midpoints instead of
``np.percentile`` interpolation.  Every other pinned field — event
counts, delivery tallies, path queries, ``latency_count`` — is
unchanged, which is the telemetry-off inertness proof; the explicit
key-absence check below pins that no telemetry/profiler field appears
at the defaults.

PR 9 (fetch/delivery cohorts): one fetch cycle per poll, and same-tick
wakeups/deliveries coalesced into cohort events.  Coalescing merges
events, so the two event-loop counters shrink in wakeup mode (each
``_notify`` wakes all waiters through one cohort event instead of one
event per consumer); ``FUSED_EVENTS`` pins those counts, and every
other PINNED field is unchanged.  ``ROW_DIGESTS`` pins every field of
each row but the wall clock, recorded at commit 1e45a58.
"""
import hashlib

import pytest

from _pins import metrics_digest, queue_phase
from repro.core import Engine, PipelineSpec
from repro.sweep import SweepSpec, run_sweep

# metrics allowed to differ across the columnar axis (the allocation
# counter is the measurement, wall clock is never compared)
ALLOC_KEYS = ("record_objects_materialized", "wall_s")

GRID = SweepSpec(
    name="ci_smoke_pin",
    axes={"n_hosts": [8, 12], "delivery": ["poll", "wakeup"]},
    base={"topology": "star", "n_brokers": 1, "n_topics": 2,
          "n_producers": 2, "rate_kbps": 16.0, "horizon": 10.0,
          "seed": 0})

# captured pre-refactor (PR 2), wall_s excluded
PINNED = {
    (8, "poll"): {
        "sim_s": 10.0, "engine_events": 1464, "events_scheduled": 1472,
        "events_cancelled": 0, "records_produced": 80,
        "records_delivered": 392, "records_expired": 0,
        "records_truncated": 0, "lost_or_partial": 2, "elections": 0,
        "isr_changes": 0, "latency_count": 392,
        "latency_mean": 0.05630281244879161,
        "latency_p50": 0.06042963902381328,
        "latency_p99": 0.10746078283213174,
        "e2e_count": 0, "e2e_sum": 0.0, "e2e_mean": 0.0,
        "reach_queries": 160, "path_queries": 1472, "reach_computes": 9,
        "max_util_pct": 0.0051024000000000095,
    },
    (8, "wakeup"): {
        "sim_s": 10.0, "engine_events": 1380, "events_scheduled": 1383,
        "events_cancelled": 0, "records_produced": 80,
        "records_delivered": 400, "records_expired": 0,
        "records_truncated": 0, "lost_or_partial": 0, "elections": 0,
        "isr_changes": 0, "latency_count": 400,
        "latency_mean": 0.0072262288401327,
        "latency_p50": 0.006042963902381328,
        "latency_p99": 0.06042963902381328,
        "e2e_count": 0, "e2e_sum": 0.0, "e2e_mean": 0.0,
        "reach_queries": 160, "path_queries": 880, "reach_computes": 9,
        "max_util_pct": 0.0051024000000000095,
    },
    (12, "poll"): {
        "sim_s": 10.0, "engine_events": 2488, "events_scheduled": 2500,
        "events_cancelled": 0, "records_produced": 80,
        "records_delivered": 704, "records_expired": 0,
        "records_truncated": 0, "lost_or_partial": 2, "elections": 0,
        "isr_changes": 0, "latency_count": 704,
        "latency_mean": 0.05644048721231185,
        "latency_p50": 0.06042963902381328,
        "latency_p99": 0.10746078283213174,
        "e2e_count": 0, "e2e_sum": 0.0, "e2e_mean": 0.0,
        "reach_queries": 172, "path_queries": 2584, "reach_computes": 13,
        "max_util_pct": 0.0051024000000000095,
    },
    (12, "wakeup"): {
        "sim_s": 10.0, "engine_events": 2340, "events_scheduled": 2343,
        "events_cancelled": 0, "records_produced": 80,
        "records_delivered": 720, "records_expired": 0,
        "records_truncated": 0, "lost_or_partial": 0, "elections": 0,
        "isr_changes": 0, "latency_count": 720,
        "latency_mean": 0.007149962732744779,
        "latency_p50": 0.006042963902381328,
        "latency_p99": 0.06042963902381328,
        "e2e_count": 0, "e2e_sum": 0.0, "e2e_mean": 0.0,
        "reach_queries": 172, "path_queries": 1520, "reach_computes": 13,
        "max_util_pct": 0.0051024000000000095,
    },
}

# PR 9: fused cohort delivery merges same-tick events, so only the two
# event-loop counters move — and only in wakeup mode (poll registers no
# waiters; the grid has one partition per topic, so no deliver cohorts).
# Every other PINNED field must stay bit-identical under fusion.
FUSED_EVENTS = {
    (8, "wakeup"): {"engine_events": 1068, "events_scheduled": 1071},
    (12, "wakeup"): {"engine_events": 1716, "events_scheduled": 1719},
}

# every metric of each row but wall_s (sha256 prefix of canonical
# JSON, tests/_pins.py), recorded at commit 1e45a58
ROW_DIGESTS = {
    (8, "poll"): "3228f0c87e77a502",
    (8, "wakeup"): "e4fce724f3e05cba",
    (12, "poll"): "b911b04834dc5639",
    (12, "wakeup"): "0965ab3cfeeff5ca",
}


def _pins(key):
    """PINNED with the fused event-count overlay (the default mode)."""
    return {**PINNED[key], **FUSED_EVENTS.get(key, {})}


@pytest.fixture(scope="module")
def rows():
    res = run_sweep(GRID, workers=1, cache_dir=None)
    return {(r["params"]["n_hosts"], r["params"]["delivery"]): r["metrics"]
            for r in res.rows}


@pytest.mark.parametrize("key", sorted(PINNED))
def test_pre_refactor_metrics_reproduced_exactly(rows, key):
    got = rows[key]
    for field, want in _pins(key).items():
        assert got[field] == want, \
            f"{key}: metrics[{field!r}] = {got[field]!r}, pinned {want!r}"


def test_new_fields_are_single_partition_shaped(rows):
    # the refactor's additions must describe the degenerate layout:
    # 2 topics x 1 partition, no groups, one batch per record
    for key, got in rows.items():
        assert got["n_partitions"] == 2
        assert got["n_groups"] == 0 and got["group_lag"] == {}
        assert got["produce_batches"] == got["records_produced"]
        assert set(got["partition_produced"]) == {"t0/0", "t1/0"}


def test_event_time_fields_are_inert_without_spes(rows):
    # no SPE in the pinned grid: the operator-graph metrics must read
    # exactly zero (they are fingerprinted, so inert means inert)
    for got in rows.values():
        for k in ("windows_fired", "window_emits", "late_records",
                  "checkpoint_count", "recovered_duplicates",
                  "spe_recoveries"):
            assert got[k] == 0, (k, got[k])


def test_telemetry_fields_are_absent_at_defaults(rows):
    # PR 7: telemetry off is the default, and off means *absent* — the
    # metrics dict gains no keys, so pre-telemetry fingerprints (and the
    # sweep cache) are untouched.  The spec-level default is also pinned:
    # build_scenario without a "telemetry" param must leave spec.telemetry
    # None (engine: zero added events, zero RNG draws).
    for got in rows.values():
        for k in ("telemetry_samples", "telemetry_series",
                  "telemetry_digest", "stage_spans", "stage_digest",
                  "lineage_records", "flight_events",
                  "profile_counts", "profile_wall"):
            assert k not in got, k


def test_chaos_backpressure_fields_are_inert_at_defaults(rows):
    # PR 6 additions: with no chaos plan, unbounded queues and a healthy
    # cluster, every degradation counter must read exactly zero — they
    # are fingerprinted, so inert means inert
    for got in rows.values():
        for k in ("produce_retries", "produce_expired", "chaos_faults",
                  "fault_events", "records_shed", "bytes_shed",
                  "backpressure_pauses", "queue_peak_bytes"):
            assert got[k] == 0, (k, got[k])
        assert got["pause_seconds"] == 0.0


def test_columnar_path_materializes_no_records(rows):
    # the default (BatchView) delivery never builds a Record at the
    # boundary — the allocation win the CI bench gates on
    for key, got in rows.items():
        assert got["record_objects_materialized"] == 0, key


def _variant_rows(**base_over):
    grid = SweepSpec(name="ci_smoke_pin_variant", axes=dict(GRID.axes),
                     base={**GRID.base, **base_over})
    # workers=1 runs inline, so a queue-phase patch reaches the engines
    res = run_sweep(grid, workers=1, cache_dir=None)
    return {(r["params"]["n_hosts"], r["params"]["delivery"]):
            r["metrics"] for r in res.rows}


@pytest.fixture(scope="module")
def record_mode_rows():
    return _variant_rows(columnar=0)


@pytest.fixture(scope="module")
def wheel_phase_rows():
    with pytest.MonkeyPatch.context() as mp:
        queue_phase(mp, "wheel")
        return _variant_rows()


@pytest.mark.parametrize("key", sorted(PINNED))
def test_record_mode_reproduces_pins_and_columnar_rows(
        rows, record_mode_rows, key):
    got = record_mode_rows[key]
    for field, want in _pins(key).items():
        assert got[field] == want, \
            f"{key} (record mode): metrics[{field!r}] = {got[field]!r}"
    # against the columnar run: everything but the allocation counter
    # (and wall clock) is bit-identical — BatchView delivery reproduces
    # the pre-refactor behavior exactly, in both delivery modes
    col = rows[key]
    assert {k: v for k, v in got.items() if k not in ALLOC_KEYS} == \
        {k: v for k, v in col.items() if k not in ALLOC_KEYS}
    assert got["record_objects_materialized"] == got["records_delivered"]


@pytest.mark.parametrize("key", sorted(PINNED))
def test_heap_scheduler_reproduces_calendar_rows(
        rows, wheel_phase_rows, key):
    # the queue's wheel phase pops in the same (t, seq) order as its
    # heap phase, so the whole row is unchanged
    got = wheel_phase_rows[key]
    for field, want in _pins(key).items():
        assert got[field] == want, \
            f"{key} (wheel): metrics[{field!r}] = {got[field]!r}"
    col = rows[key]
    assert {k: v for k, v in got.items() if k != "wall_s"} == \
        {k: v for k, v in col.items() if k != "wall_s"}


@pytest.mark.parametrize("key", sorted(PINNED))
def test_legacy_fetch_mode_reproduces_original_pins_exactly(rows, key):
    # every field of the row, not only PINNED's subset: event counters,
    # per-partition tallies, histograms and the degradation counters
    assert metrics_digest(rows[key]) == ROW_DIGESTS[key], key


# ---------------------------------------------------------------------------
# PR 4 pin: processing-time SPE pipeline (pre-operator-graph capture)
# ---------------------------------------------------------------------------


def word_count_spec(delivery, columnar=True):
    docs = ["to be or not to be", "be the change", "stream all things",
            "not all who wander are lost"]
    spec = PipelineSpec(delivery=delivery, columnar=columnar)
    spec.add_switch("s1")
    for h in ["b", "h1", "h2", "h3", "h4"]:
        spec.add_host(h).add_link(h, "s1", lat=1.0, bw=1000.0)
    spec.add_broker("b")
    for t in ["raw", "words", "counts"]:
        spec.add_topic(t, leader="b")
    spec.add_producer("h1", "DIRECTORY", topic="raw", docs=docs,
                      totalMessages=8, interval=0.3)
    spec.add_spe("h2", query="split", inTopic="raw", outTopic="words",
                 pollInterval=0.05)
    spec.add_spe("h3", query="count", inTopic="words", outTopic="counts",
                 window=0.5, pollInterval=0.05)
    spec.add_consumer("h4", "METRICS", topic="counts", pollInterval=0.05)
    return spec


# captured at the PR 3 head (monolithic Query runtime), seed 0,
# run until sim t=20: engine events, e2e aggregates at full precision,
# and a sha256 digest of the sink's payload sequence
SPE_PINNED = {
    "poll": {
        "engine_events": 1352, "events_scheduled": 1357,
        "records_produced": 24, "records_delivered": 24,
        "e2e_count": 8, "e2e_sum": 2.781267564459786,
        "produce_batches": 24,
    },
    "wakeup": {
        "engine_events": 184, "events_scheduled": 186,
        "records_produced": 24, "records_delivered": 24,
        "e2e_count": 8, "e2e_sum": 2.6567097619999998,
        "produce_batches": 24,
    },
}
SPE_SINK_DIGEST = "f0f84300d0db8d91"


@pytest.mark.parametrize("columnar", [True, False],
                         ids=["batchview", "records"])
@pytest.mark.parametrize("delivery", sorted(SPE_PINNED))
def test_processing_time_spe_pipeline_reproduced_exactly(delivery,
                                                         columnar):
    eng = Engine(word_count_spec(delivery, columnar), seed=0)
    eng.run(until=20.0)
    got = eng.metrics()
    for field, want in SPE_PINNED[delivery].items():
        assert got[field] == want, \
            f"{delivery}: metrics[{field!r}] = {got[field]!r}, " \
            f"pinned {want!r}"
    sink = [rt for rt in eng.runtimes
            if rt.name.startswith("consumer")][0]
    digest = hashlib.sha256(repr(sink.payloads).encode()).hexdigest()[:16]
    assert digest == SPE_SINK_DIGEST, \
        "SPE output payload sequence diverged from the pre-refactor pin"
    # processing-time mode exercises no event-time machinery
    for k in ("windows_fired", "late_records", "checkpoint_count",
              "recovered_duplicates"):
        assert got[k] == 0
    # the delivery boundary: BatchViews materialize nothing, the record
    # path pays one Record per delivered row
    want_mat = 0 if columnar else got["records_delivered"]
    assert got["record_objects_materialized"] == want_mat
