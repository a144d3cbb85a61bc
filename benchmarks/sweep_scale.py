"""Scale benchmark: generated topologies at 100-1000 emulated nodes.

Demonstrates the "several hundred emulated nodes" scale target on
sweep-generated geo-WAN topologies: 3 replicated brokers, 10 synthetic
producers, every remaining host a consumer, plus a mid-run broker
partition (elections + ISR churn exercise the controller loop and the
routing-table invalidation path).

Three claims, all recorded in ``BENCH_sweep_scale.json``, and one pin:

1. **Scale** — scenarios at 100/200/400/1000 nodes complete in
   (multiples of) real time, with a **per-phase timing breakdown** so
   regressions point at a layer instead of a number: ``build_spec_s``
   (topology generation + spec assembly), ``engine_init_s``
   (cluster/runtime construction), ``run_s`` (the event loop — the
   number that must stay above real time), ``metrics_s`` (result
   aggregation).  The phase split needs intra-run timers, so the sizes
   run directly on :class:`Engine` rather than through the sweep
   runner; ``sim_s_per_wall_s`` divides by the run phase, same as the
   sweep runner's ``wall_s``.  The headline numbers always come from an
   **unprofiled** run; ``--profile`` adds a *separate* instrumented
   pass per size (telemetry + engine profiler) whose wall shares land
   under ``sizes[n].profile`` — profiling overhead never contaminates
   the sim-rate claim.
2. **Routing tables** — ``route_mode="table"`` (the default) replaces
   per-source on-demand SSSP with one vectorized all-pairs pass per
   network epoch.  The before/after pair runs the identical chaotic
   scenario under both modes and **asserts bit-identity** — engine
   event counts equal and the deterministic-metrics fingerprints equal
   (routing tables must be a pure optimization) — then gates on the
   deterministic reduction in shortest-path solver invocations
   (``Network.n_route_solves``: nx SSSP runs on demand vs table builds;
   the path-query cost that the tables amortize) being at least
   ``MIN_ROUTE_SOLVE_REDUCTION``x.  Both counters are exact and
   seed-stable, so the gate never flakes on wall clock.
3. **Reachability caching** — the per-network-epoch memoization
   (connected components for ``reachable``) collapses the controller's
   O(topics x brokers) probe loop; the ``reach_cache`` before/after
   pair asserts identical event counts and gates ``probe_reduction``.

Schema::

    {
      "sizes": {n: {engine_events, wall_s, sim_s_per_wall_s,
                    records_delivered, elections, reach_queries,
                    path_queries, reach_computes, route_solves,
                    record_objects_materialized,
                    phases: {build_spec_s, engine_init_s, run_s,
                             metrics_s},
                    profile?: {counts, wall_s, path_query_count,
                               path_query_share}}},
      "route_mode_compare": {n_hosts, horizon_sim_s,
                             events_ondemand, events_table,
                             solves_ondemand, solves_table,
                             path_queries, solve_reduction,
                             fingerprint_ondemand, fingerprint_table,
                             fingerprints_equal, events_equal},
      "reach_cache_compare": {n_hosts, horizon_sim_s,
                              events_uncached, events_cached,
                              computes_uncached, computes_cached,
                              probe_reduction, events_equal}
    }

4. **Delivery cohorts** — the broker coalesces same-tick wakeup and
   deliver events into cohort events.  One chaotic multi-partition
   scenario at the compare size must reproduce the exact
   ``engine_events`` and deterministic-metrics fingerprint recorded at
   commit 1e45a58 (``COHORT_PINS``).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from repro.core import Engine  # noqa: E402
from repro.sweep import SweepSpec, run_sweep  # noqa: E402
from repro.sweep.results import TIMING_KEYS  # noqa: E402
from repro.sweep.scenarios import build_scenario  # noqa: E402
from benchmarks.common import emit  # noqa: E402

# caching/tables must not change behavior, only skip recomputation:
# asserted on the compare pairs; thresholds sit well below the observed
# reductions to avoid flaking, and both ratios are deterministic counts
MIN_PROBE_REDUCTION = 5.0
MIN_ROUTE_SOLVE_REDUCTION = 5.0
# the cohort scenario per (n_hosts, horizon): exact engine events and
# metrics_fingerprint, recorded at commit 1e45a58
COHORT_PINS = {
    (60, 4.0): (2116, "c52c1974afa15a646ce0ba4c39a854643a039ce2afe137398f"
                      "572c472e3f7aa5"),
    (200, 6.0): (10252, "9c881ae4af94796afd56bfa43c614700c30a20bf3ba3a6d3"
                        "58e95cc065fe1fc1"),
}


def scale_base(horizon: float) -> dict:
    return {
        "topology": "geo_wan",
        "topo": {"extra_edge_frac": 0.25},
        "n_brokers": 3, "replication": 3, "n_topics": 10,
        "n_producers": 10, "rate_kbps": 8.0, "msg_size": 512,
        "poll_interval": 0.2, "delivery": "wakeup",
        "fault": "partition", "fault_at": horizon * 0.3,
        "fault_duration": horizon * 0.2,
        "horizon": horizon, "seed": 0,
    }


# wall clock plus the diagnostic solver counter, which differs between
# route modes *by design* (it is the work the tables amortize away)
_NONDET_KEYS = frozenset(TIMING_KEYS) | {"route_solves", "phases"}


def metrics_fingerprint(m: dict) -> str:
    """Hash over the deterministic metrics of one engine run (the
    single-scenario analogue of ``SweepResults.fingerprint``)."""
    det = {k: v for k, v in m.items() if k not in _NONDET_KEYS}
    blob = json.dumps(det, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()


def run_sized(n_hosts: int, horizon: float, profile: bool = False,
              extra: dict | None = None) -> dict:
    """One instrumented scale point: per-phase wall-clock breakdown."""
    params = {**scale_base(horizon), "n_hosts": n_hosts, **(extra or {})}
    if profile:
        params.update(telemetry=1.0, profile=1)
    t0 = time.perf_counter()
    spec = build_scenario(params)
    t1 = time.perf_counter()
    eng = Engine(spec, seed=int(params["seed"]))
    t2 = time.perf_counter()
    eng.run(until=horizon)
    t3 = time.perf_counter()
    m = eng.metrics(wall_s=t3 - t2)
    t4 = time.perf_counter()
    m["phases"] = {
        "build_spec_s": t1 - t0,
        "engine_init_s": t2 - t1,
        "run_s": t3 - t2,
        "metrics_s": t4 - t3,
    }
    m["route_solves"] = eng.net.n_route_solves
    if profile:
        # in-engine phase accounting (repro.core.telemetry.Profiler):
        # which layer the run phase actually spends its wall clock in,
        # and the netem path-query share the routing tables must hold
        # down.  Shares are relative to this instrumented run's wall —
        # the headline sim rate comes from the unprofiled pass.
        wall, run_s = dict(m["profile_wall"]), t3 - t2
        m["profile"] = {
            "counts": dict(m["profile_counts"]),
            "wall_s": wall,
            "path_query_count": m["profile_counts"]["netem_path"],
            "path_query_share": wall.get("netem_path", 0.0) / run_s,
        }
    return m


def _compare_route_modes(n_hosts: int, horizon: float) -> dict:
    """Identical chaotic scenario under both route modes: bit-identity
    asserted, deterministic solver-reduction gated."""
    runs = {}
    for mode in ("ondemand", "table"):
        m = run_sized(n_hosts, horizon,
                      extra={"route_mode": mode, "chaos": 2})
        m.pop("phases")
        runs[mode] = m
    before, after = runs["ondemand"], runs["table"]
    fp_b, fp_a = metrics_fingerprint(before), metrics_fingerprint(after)
    assert before["engine_events"] == after["engine_events"], \
        "routing tables changed simulation behavior " \
        f"({before['engine_events']} != {after['engine_events']} events)"
    assert fp_b == fp_a, \
        "route modes disagree on deterministic metrics:\n" + "\n".join(
            f"  {k}: {before[k]!r} != {after[k]!r}"
            for k in sorted(before)
            if k not in _NONDET_KEYS and before[k] != after[k])
    reduction = before["route_solves"] / max(1, after["route_solves"])
    assert reduction >= MIN_ROUTE_SOLVE_REDUCTION, \
        f"routing tables regressed: {reduction:.1f}x < " \
        f"{MIN_ROUTE_SOLVE_REDUCTION}x solver reduction " \
        f"({before['route_solves']} -> {after['route_solves']} solves " \
        f"for {after['path_queries']} path queries)"
    return {
        "n_hosts": n_hosts,
        "horizon_sim_s": horizon,
        "events_ondemand": before["engine_events"],
        "events_table": after["engine_events"],
        "solves_ondemand": before["route_solves"],
        "solves_table": after["route_solves"],
        "path_queries": after["path_queries"],
        "solve_reduction": reduction,
        "fingerprint_ondemand": fp_b,
        "fingerprint_table": fp_a,
        "fingerprints_equal": True,
        "events_equal": True,
    }


def _check_cohort_pin(n_hosts: int, horizon: float) -> dict:
    """The chaotic multi-partition scenario against its recorded event
    count and fingerprint."""
    m = run_sized(n_hosts, horizon, extra={"chaos": 2, "partitions": 4})
    m.pop("phases")
    got = (m["engine_events"], metrics_fingerprint(m))
    assert got == COHORT_PINS[(n_hosts, horizon)], \
        f"cohort scenario ({n_hosts} hosts, {horizon} s) changed: " \
        f"{got} != recorded {COHORT_PINS[(n_hosts, horizon)]}"
    return {"engine_events": got[0],
            "records_delivered": m["records_delivered"]}


def run(*, smoke: bool = False, full: bool = False, profile: bool = False,
        out: str = "BENCH_sweep_scale.json") -> dict:
    # `full` kept for compat; 400 and 1000 nodes are part of the record
    sizes = [60] if smoke else [100, 200, 400, 1000]
    horizon = 8.0 if smoke else 20.0
    results: dict = {"sizes": {}}

    for n in sizes:
        m = run_sized(n, horizon)
        results["sizes"][n] = {
            "engine_events": m["engine_events"],
            "wall_s": m["wall_s"],
            "sim_s_per_wall_s": m["sim_s"] / m["wall_s"],
            "records_delivered": m["records_delivered"],
            "elections": m["elections"],
            "reach_queries": m["reach_queries"],
            "path_queries": m["path_queries"],
            "reach_computes": m["reach_computes"],
            "route_solves": m["route_solves"],
            "record_objects_materialized":
                m["record_objects_materialized"],
            "phases": m["phases"],
        }
        emit(f"sweep_scale/{n}nodes", m["wall_s"] * 1e6,
             f"events={m['engine_events']};"
             f"delivered={m['records_delivered']};"
             f"route_solves={m['route_solves']};"
             f"sim_rate={m['sim_s'] / m['wall_s']:.1f}x")
        if profile:
            # separate instrumented pass: never reuse its wall clock
            p = run_sized(n, horizon, profile=True)
            results["sizes"][n]["profile"] = p["profile"]
            emit(f"sweep_scale/{n}nodes_profile",
                 p["profile"]["wall_s"].get("netem_path", 0.0) * 1e6,
                 f"path_queries={p['profile']['path_query_count']};"
                 f"path_share={p['profile']['path_query_share']:.3f};"
                 f"ops={p['profile']['counts'].get('operator', 0)}")

    # routing tables vs on-demand SSSP on one identical chaotic scenario
    cmp_n = 60 if smoke else 200
    cmp_h = 4.0 if smoke else 6.0
    results["route_mode_compare"] = rm = _compare_route_modes(cmp_n, cmp_h)
    emit("sweep_scale/route_mode", 0.0,
         f"solve_reduction={rm['solve_reduction']:.0f}x;"
         f"solves={rm['solves_ondemand']}->{rm['solves_table']};"
         f"path_queries={rm['path_queries']};"
         f"fingerprints_equal={rm['fingerprints_equal']}")

    # the delivery-cohort scenario against its recorded pin
    cp = _check_cohort_pin(cmp_n, cmp_h)
    emit("sweep_scale/cohort_pin", 0.0,
         f"events={cp['engine_events']};"
         f"delivered={cp['records_delivered']}")

    # before/after reachability caching on one identical scenario
    pair_sweep = SweepSpec(
        name="sweep_scale_reach_cache",
        axes={"reach_cache": [False, True]},
        base={**scale_base(cmp_h), "n_hosts": cmp_n})
    pair = {row["params"]["reach_cache"]: row["metrics"]
            for row in run_sweep(pair_sweep, workers=1, cache_dir=None).rows}
    before, after = pair[False], pair[True]
    assert before["engine_events"] == after["engine_events"], \
        "reachability caching changed simulation behavior " \
        f"({before['engine_events']} != {after['engine_events']} events)"
    reduction = before["reach_computes"] / max(1, after["reach_computes"])
    assert reduction >= MIN_PROBE_REDUCTION, \
        f"reachability cache regressed: {reduction:.1f}x < " \
        f"{MIN_PROBE_REDUCTION}x probe reduction"
    results["reach_cache_compare"] = {
        "n_hosts": cmp_n,
        "horizon_sim_s": cmp_h,
        "events_uncached": before["engine_events"],
        "events_cached": after["engine_events"],
        "computes_uncached": before["reach_computes"],
        "computes_cached": after["reach_computes"],
        "probe_reduction": reduction,
        "events_equal": True,
    }
    emit("sweep_scale/reach_cache", 0.0,
         f"probe_reduction={reduction:.0f}x;"
         f"events={after['engine_events']};"
         f"wall_uncached={before['wall_s']:.1f}s;"
         f"wall_cached={after['wall_s']:.1f}s")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (60 nodes)")
    ap.add_argument("--full", action="store_true",
                    help="compat flag (400/1000 nodes run by default)")
    ap.add_argument("--profile", action="store_true",
                    help="add a separate profiled pass per size "
                         "(telemetry=1s + engine profiler): call counts "
                         "and wall shares land under sizes[n].profile; "
                         "headline sim rates stay unprofiled")
    ap.add_argument("--out", default="BENCH_sweep_scale.json")
    args = ap.parse_args()
    res = run(smoke=args.smoke, full=args.full, profile=args.profile,
              out=args.out)
    print(json.dumps(res["route_mode_compare"], indent=2))
