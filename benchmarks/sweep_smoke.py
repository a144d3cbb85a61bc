"""CI sweep smoke: tiny 2x2x2x2 grid, warm workers, resume + determinism.

Runs a 2x2x2x2 grid (topology size x delivery mode x topic partitions x
windowed operator pipeline) on 2 **warm-pool** workers (forkserver with
the lazy-JAX preload where the platform has it, spawn fallback),
deletes part of the per-scenario cache, reruns, and asserts:

- the rerun reuses the surviving cache entries (resume) — the
  kill-anywhere contract is unchanged by the warm pool, since workers
  still write each scenario's row atomically themselves;
- the resumed aggregate equals the uninterrupted run's fingerprint —
  event counts and all other deterministic metrics identical (wall
  clock is excluded from the fingerprint, as in the bench smoke);
- the second sweep ran on the *same* persistent worker pool (zero new
  interpreter/numpy starts — the warm-worker claim, gated).

The ``partitions`` axis makes the gate cover the per-partition hash
fields; the ``windowed`` axis adds an event-time tumbling-window SPE
(checkpointing on) so the event-time metrics — ``windows_fired``,
``late_records``, ``checkpoint_count``, ``recovered_duplicates`` —
enter the fingerprint: any cross-process nondeterminism in watermark
propagation or pane firing fails CI here.

``--chaos`` appends a second, independent grid (own cache dir) that
drives a seeded chaos plan over bounded-queue subscribers across both
delivery modes and shed policies, gating:

- resume-fingerprint equality on the chaos grid (a seed names one
  adversarial run, bit-identically, across cache interruption);
- ``records_shed`` > 0 under the shedding policy and == 0 under pause
  (backpressure must throttle, never drop);
- produce-side degradation counters (``produce_retries``,
  ``chaos_faults``, ``fault_events``) identical across the two delivery
  modes for otherwise-identical params — the chaos schedule and
  producer-side protocol randomness must not see the consumer loop.

``--fetch`` runs the delivery-cohort gates over the chaotic
bounded-queue grid with four partitions per topic, in both delivery
modes:

- each row's exact ``engine_events`` and ``events_scheduled`` equal
  the counts recorded at commit 1e45a58 (``FETCH_PINS``), so a change
  that merges or splits events shows here, never through wall clock;
- per-message sink digests (which consumer got which record, when) on
  a direct engine run equal the recorded ones;
- the grid sheds records (the overload is real).

``--telemetry`` runs the observability gates (the CI ``obs-smoke`` job):

- telemetry artifacts (series digests, stage-span histograms, flight
  and profiler call counts) bit-identical across warm-pool processes
  and columnar on/off;
- telemetry-on adds only its own sampler events and < 5% extra engine
  events on the smoke scenario, perturbing no other metric;
- telemetry off (param absent or 0) is byte-for-byte inert;
- the exported Chrome trace is valid JSON under the schema subset
  Perfetto loads (``repro.obs.trace.validate_chrome_trace``).

Exits non-zero on any gate failure; CI runs it on every PR.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))

from repro.sweep import SweepSpec, run_sweep, warm_pool_pids  # noqa: E402

CACHE = ".ci_sweep"
CHAOS_CACHE = ".ci_sweep_chaos"
TEL_CACHE = ".ci_sweep_tel"

sweep = SweepSpec(
    name="ci_smoke",
    axes={"n_hosts": [8, 12], "delivery": ["poll", "wakeup"],
          "partitions": [1, 2], "windowed": [0, 1]},
    base={"topology": "star", "n_brokers": 1, "n_topics": 2,
          "n_producers": 2, "rate_kbps": 16.0, "horizon": 10.0,
          "window_s": 1.0, "et_jitter_s": 0.5,
          "checkpoint_interval": 2.0, "seed": 0})


chaos_sweep = SweepSpec(
    name="ci_chaos_smoke",
    axes={"delivery": ["poll", "wakeup"],
          "shed_policy": ["pause", "drop_oldest"]},
    base={"topology": "geo_wan", "n_hosts": 8, "n_brokers": 3,
          "replication": 3, "n_topics": 2, "n_producers": 2,
          "rate_kbps": 256.0, "msg_size": 512, "consumer_cost": 0.02,
          "queue_bytes": 16 << 10, "chaos": 1,
          "horizon": 6.0, "seed": 0})


def chaos_main() -> None:
    """The --chaos gates: seeded adversarial grid, resumable + split by
    policy exactly as documented (shed vs throttle), produce side blind
    to the delivery mode."""
    shutil.rmtree(CHAOS_CACHE, ignore_errors=True)
    a = run_sweep(chaos_sweep, workers=2, cache_dir=CHAOS_CACHE,
                  progress=print)
    assert len(a) == 4 and a.n_cached == 0
    for p in sorted(glob.glob(os.path.join(CHAOS_CACHE, "*.json")))[:2]:
        os.remove(p)
    b = run_sweep(chaos_sweep, workers=2, cache_dir=CHAOS_CACHE,
                  progress=print)
    assert b.n_cached == 2, "chaos resume must reuse the surviving cache"
    assert a.fingerprint() == b.fingerprint(), \
        "resumed chaos sweep diverged (shed/fault counters included)"
    rows = {(r["params"]["delivery"], r["params"]["shed_policy"]):
            r["metrics"] for r in a.rows}
    for (delivery, policy), m in sorted(rows.items()):
        assert m["chaos_faults"] > 0, "chaos plan expanded to nothing"
        assert m["fault_events"] > 0, "no chaos fault ever applied"
        if policy == "pause":
            assert m["records_shed"] == 0, \
                f"pause policy shed records ({delivery})"
            assert m["backpressure_pauses"] > 0, \
                f"overloaded pause grid never paused ({delivery})"
        else:
            assert m["records_shed"] > 0, \
                f"shedding grid point shed nothing ({delivery}/{policy})"
    for policy in ("pause", "drop_oldest"):
        mp, mw = rows[("poll", policy)], rows[("wakeup", policy)]
        for k in ("chaos_faults", "produce_retries", "records_produced"):
            assert mp[k] == mw[k], \
                f"{k} differs across delivery modes ({policy}): " \
                f"{mp[k]} != {mw[k]}"
    print(a.table())
    print("chaos smoke ok | shed(drop_oldest/wakeup):",
          rows[("wakeup", "drop_oldest")]["records_shed"],
          "| pauses(pause/wakeup):",
          rows[("wakeup", "pause")]["backpressure_pauses"])


FETCH_CACHE = ".ci_sweep_fetch"

# the chaotic bounded-queue base with multiple partitions per topic so
# deliver cohorts actually form
fetch_sweep = SweepSpec(
    name="ci_fetch_smoke",
    axes={"delivery": ["poll", "wakeup"]},
    base={**chaos_sweep.base, "partitions": 4,
          "shed_policy": "drop_oldest"})

# per delivery mode: (engine_events, events_scheduled, sink digest),
# recorded at commit 1e45a58
FETCH_PINS = {
    "poll": (3223, 3237, "ee1e37949c7f987f"),
    "wakeup": (4257, 4268, "05235c0bec7f8cf1"),
}


def fetch_main() -> None:
    """The --fetch gates: exact event counts and sink digests against
    the recorded ones, and a grid that sheds."""
    import hashlib

    from repro.core.engine import Engine
    from repro.sweep.scenarios import build_scenario

    shutil.rmtree(FETCH_CACHE, ignore_errors=True)
    a = run_sweep(fetch_sweep, workers=2, cache_dir=FETCH_CACHE,
                  progress=print)
    assert len(a) == 2 and a.n_cached == 0
    for r in a.rows:
        delivery, m = r["params"]["delivery"], r["metrics"]
        p = {**fetch_sweep.base, "delivery": delivery}
        eng = Engine(build_scenario(p), seed=int(p["seed"]))
        mon = eng.run(until=float(p["horizon"]))
        blob = repr([(mid, sorted(msg.deliveries.items()))
                     for mid, msg in sorted(mon.msgs.items())])
        got = (m["engine_events"], m["events_scheduled"],
               hashlib.sha256(blob.encode()).hexdigest()[:16])
        assert got == FETCH_PINS[delivery], \
            f"{delivery}: (events, scheduled, sink) {got} != " \
            f"recorded {FETCH_PINS[delivery]}"
        assert m["records_shed"] > 0, \
            f"the overload grid must exercise shedding ({delivery})"
    print(a.table())
    print("fetch smoke ok |", ", ".join(
        f"{d}: {v[0]} events, sink {v[2]}"
        for d, v in sorted(FETCH_PINS.items())))


tel_sweep = SweepSpec(
    name="ci_tel_smoke",
    axes={"columnar": [0, 1]},
    base={**chaos_sweep.base, "consumer_groups": 1,
          "telemetry": 0.5, "profile": 1, "lineage_k": 2})


def telemetry_main() -> None:
    """The --telemetry gates (CI obs-smoke job): cross-axis bit-identity
    of every telemetry artifact, < 5% event overhead, telemetry-off
    inertness, and a schema-valid Chrome trace export."""
    from repro.core.engine import Engine
    from repro.obs.trace import validate_chrome_trace
    from repro.sweep.scenarios import build_scenario

    shutil.rmtree(TEL_CACHE, ignore_errors=True)
    os.makedirs(TEL_CACHE)
    a = run_sweep(tel_sweep, workers=2, cache_dir=TEL_CACHE,
                  progress=print)
    assert len(a) == 2 and a.n_cached == 0
    rows = {r["params"]["columnar"]: r["metrics"] for r in a.rows}
    ref = rows[1]
    for key, m in sorted(rows.items()):
        for k in ("telemetry_digest", "stage_digest", "telemetry_samples",
                  "telemetry_series", "stage_spans", "flight_events",
                  "lineage_records", "profile_counts"):
            assert m[k] == ref[k], \
                f"{k} differs across the columnar axis ({key})"

    def _run(params):
        eng = Engine(build_scenario(params), seed=int(params["seed"]))
        return eng, eng.run_metrics(until=float(params["horizon"]))

    base = dict(chaos_sweep.base)
    _, m_off = _run(base)                          # telemetry param absent
    _, m_zero = _run({**base, "telemetry": 0.0})   # explicit zero
    assert {k: v for k, v in m_off.items() if k != "wall_s"} == \
        {k: v for k, v in m_zero.items() if k != "wall_s"}, \
        "telemetry=0 must be byte-for-byte inert"
    eng_on, m_on = _run({**base, "telemetry": 0.5, "profile": 1,
                         "lineage_k": 2})
    extra = m_on["engine_events"] - m_off["engine_events"]
    assert extra == m_on["telemetry_samples"], \
        "telemetry added events beyond its own sampler ticks"
    overhead = extra / m_off["engine_events"]
    assert overhead < 0.05, \
        f"telemetry event overhead {overhead:.1%} breaches the 5% gate"
    for k, v in m_off.items():
        if k in ("engine_events", "events_scheduled", "wall_s"):
            continue
        assert m_on[k] == v, \
            f"telemetry-on perturbed non-telemetry metric {k}"
    trace_path = os.path.join(TEL_CACHE, "trace.json")
    obj = eng_on.export_trace(trace_path)
    problems = validate_chrome_trace(obj)
    assert not problems, f"exported trace invalid: {problems[:3]}"
    with open(trace_path) as f:
        reloaded = json.load(f)
    assert validate_chrome_trace(reloaded) == []
    print(a.table())
    print(f"telemetry smoke ok | samples: {m_on['telemetry_samples']} "
          f"| event overhead: {overhead:.2%} "
          f"| flight events: {m_on['flight_events']} "
          f"| trace events: {len(obj['traceEvents'])}")


def main() -> None:
    shutil.rmtree(CACHE, ignore_errors=True)
    a = run_sweep(sweep, workers=2, cache_dir=CACHE, progress=print)
    assert len(a) == 16 and a.n_cached == 0
    pids = warm_pool_pids()
    assert len(pids) == 2, "first sweep must leave a live warm pool"
    for p in sorted(glob.glob(os.path.join(CACHE, "*.json")))[:5]:
        os.remove(p)
    b = run_sweep(sweep, workers=2, cache_dir=CACHE, progress=print)
    assert b.n_cached == 11, "resume must reuse the surviving cache"
    assert a.fingerprint() == b.fingerprint(), \
        "resumed sweep diverged from the uninterrupted run"
    assert warm_pool_pids() == pids, \
        "second sweep must reuse the warm worker pool"
    events = a.total("engine_events")
    assert events == b.total("engine_events") and events > 0
    fired = sum(r["metrics"]["windows_fired"] for r in a.rows
                if r["params"]["windowed"])
    assert fired > 0, "windowed scenarios must actually fire windows"
    print(a.table())
    print("aggregate engine events:", events,
          "| windows fired:", fired)


if __name__ == "__main__":
    if "--chaos" in sys.argv[1:]:
        chaos_main()
    elif "--telemetry" in sys.argv[1:]:
        telemetry_main()
    elif "--fetch" in sys.argv[1:]:
        fetch_main()
    else:
        main()
