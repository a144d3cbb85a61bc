"""Vectorized routing tables vs on-demand SSSP: bit-identity (PR 8).

``route_mode="table"`` (the default) must be *indistinguishable* from
the legacy per-source networkx Dijkstra — not approximately: engine
event streams, monitor event logs, per-message delivery times, every
fingerprinted metric including the ``path_queries``/``reach_computes``
counters, across both delivery modes, both queue phases, and under an
active chaos plan whose flapping links / crashes force repeated epoch
invalidation (plus gray-loss ramps exercising the ``loss_epoch`` seam
and slow-host faults exercising the no-invalidation query-time extras).

The fuzz section asserts the numeric core directly on random graphs:
table path latencies equal networkx Dijkstra distances bitwise, hop
paths equal ``nx.single_source_dijkstra_path`` exactly (including
tie-heavy uniform-weight graphs, where the equal-cost fallback must
reproduce networkx's tie-break), and ``transfer``/``transfer_many``
agree between modes draw-for-draw.
"""
import random

import networkx as nx
import pytest

from _hyp import given, settings, strategies as st
from _pins import PHASES, queue_phase

from repro.core import Engine
from repro.core.netem import LinkCfg, Network
from repro.core.telemetry import Profiler
from repro.sweep import topologies
from repro.sweep.scenarios import build_scenario


# ---------------------------------------------------------------------------
# Fuzz: table values == networkx, including equal-cost ties
# ---------------------------------------------------------------------------


def random_net(seed: int, n: int, uniform: bool) -> Network:
    """A random connected-ish topology; uniform=True forces equal-cost
    multipath (the tie-break fallback path)."""
    rng = random.Random(seed)
    g = nx.gnm_random_graph(n, rng.randrange(n - 1, n * (n - 1) // 2 + 1),
                            seed=seed)
    net = Network()
    for i in range(n):
        net.add_host(f"h{i}")
    for a, b in g.edges:
        lat = 1.0 if uniform else rng.uniform(0.05, 20.0)
        net.add_link(f"h{a}", f"h{b}",
                     LinkCfg(lat_ms=lat, bw_mbps=rng.uniform(1.0, 500.0),
                             loss_pct=rng.choice([0.0, 0.0, 5.0, 40.0])))
    return net


@pytest.mark.parametrize("uniform", [False, True],
                         ids=["random-lat", "uniform-lat-ties"])
@pytest.mark.parametrize("seed", range(6))
def test_table_matches_networkx_dijkstra(seed, uniform):
    net = random_net(seed, 4 + seed * 2, uniform)
    hosts = net.hosts()
    for src in hosts:
        ref = nx.single_source_dijkstra_path(net._live_graph(), src,
                                             weight="weight")
        for dst in hosts:
            p = net.path(src, dst)
            assert p == ref.get(dst), (src, dst)
            if p is not None:
                want = sum(net.link(a, b).lat_s for a, b in zip(p, p[1:]))
                assert net.path_latency_s(src, dst) == want


@pytest.mark.parametrize("uniform", [False, True],
                         ids=["random-lat", "uniform-lat-ties"])
@pytest.mark.parametrize("seed", range(4))
def test_transfer_bit_identical_between_modes(seed, uniform):
    table = random_net(seed, 10, uniform)
    legacy = random_net(seed, 10, uniform)
    legacy.route_mode = "ondemand"
    hosts = table.hosts()
    r1, r2 = random.Random(99), random.Random(99)
    for src in hosts:
        for dst in hosts:
            for nbytes in (0, 777, 10**6):
                a = table.transfer(src, dst, nbytes, r1)
                b = legacy.transfer(src, dst, nbytes, r2)
                assert a == b, (src, dst, nbytes)
    assert r1.getstate() == r2.getstate()   # same number of draws
    assert table.n_path_queries == legacy.n_path_queries
    assert table.n_graph_builds == legacy.n_graph_builds


def test_transfer_many_matches_per_destination_transfers():
    table = random_net(3, 12, False)
    legacy = random_net(3, 12, False)
    legacy.route_mode = "ondemand"
    table.set_host_slow("h2", 0.25)
    legacy.set_host_slow("h2", 0.25)
    table.set_host_up("h5", False)
    legacy.set_host_up("h5", False)
    dsts = [f"h{i}" for i in (1, 2, 5, 0, 11, 7)] + ["nope"]
    r1, r2 = random.Random(7), random.Random(7)
    got = table.transfer_many("h0", dsts, 4096, r1)
    want = [legacy.transfer("h0", d, 4096, r2) for d in dsts]
    assert got == want
    assert r1.getstate() == r2.getstate()
    assert table.n_path_queries == legacy.n_path_queries
    assert table.n_graph_builds == legacy.n_graph_builds
    assert table.transfer_many("h0", [], 1, r1) == []


def _fan_out_pair(seed):
    """Two copies of one table-mode network: a slow source h0, a slow
    destination h2 and a down host h5."""
    nets = (random_net(seed, 12, seed % 2 == 1),
            random_net(seed, 12, seed % 2 == 1))
    for net in nets:
        net.set_host_slow("h0", 0.125)
        net.set_host_slow("h2", 0.25)
        net.set_host_up("h5", False)
    return nets


@pytest.mark.parametrize("seed,k", [(0, 0), (1, 1), (2, 2), (3, 2),
                                    (4, 5), (5, 5), (6, 32), (7, 32)])
def test_transfer_many_matches_table_transfer_loop(seed, k):
    """The table-mode fan-out answers each destination as ``transfer``
    does on a copy of the same network: results, RNG draws and
    counters, across a keep rebuild that drops the row cache mid-run
    and from a down source."""
    many, loop = _fan_out_pair(seed)
    pool = ["h2", "h5", "nope", "h0"] + [f"h{i}" for i in range(1, 12)]
    dsts = [pool[(seed + i) % len(pool)] for i in range(k)]
    r1, r2 = random.Random(seed), random.Random(seed)

    def fan_out(src, nbytes):
        got = many.transfer_many(src, dsts, nbytes, r1)
        want = [loop.transfer(src, d, nbytes, r2) for d in dsts]
        assert got == want
        assert r1.getstate() == r2.getstate()
        assert many.n_path_queries == loop.n_path_queries
        assert many.n_graph_builds == loop.n_graph_builds

    fan_out("h0", 4096)
    fan_out("h3", 100)
    for net in (many, loop):          # every route out of h0 now drops
        for a, b in sorted(many.g.edges("h0")):
            net.set_link_loss(a, b, 100.0)
    fan_out("h0", 4096)
    fan_out("h5", 100)                # the down source
    assert len(many.transfer_many("h5", dsts, 1, r1)) == k


def test_transfer_many_is_accounted_under_netem_path():
    net = random_net(1, 6, False)
    net.profiler = Profiler()
    net.transfer_many("h0", ["h1", "h2"], 512, random.Random(0))
    assert net.profiler.wall["netem_path"] > 0.0
    assert net.n_path_queries == 2


@given(seed=st.integers(0, 10**6), n=st.integers(2, 9))
@settings(max_examples=25, deadline=None)
def test_table_matches_ondemand_across_transitions(seed, n):
    """Fault transitions (epoch bumps) keep the modes in lockstep."""
    table = random_net(seed, n, seed % 2 == 0)
    legacy = random_net(seed, n, seed % 2 == 0)
    legacy.route_mode = "ondemand"
    hosts = table.hosts()
    rng = random.Random(seed ^ 0xBEEF)
    edges = sorted(tuple(sorted(e)) for e in table.g.edges)
    for _ in range(4):
        k = rng.randrange(4)
        if k == 0 and edges:
            a, b = edges[rng.randrange(len(edges))]
            up = rng.random() < 0.5
            table.set_link_up(a, b, up)
            legacy.set_link_up(a, b, up)
        elif k == 1:
            h = hosts[rng.randrange(len(hosts))]
            up = rng.random() < 0.5
            table.set_host_up(h, up)
            legacy.set_host_up(h, up)
        elif k == 2 and edges:
            a, b = edges[rng.randrange(len(edges))]
            pct = rng.choice([0.0, 15.0, 60.0])
            table.set_link_loss(a, b, pct)
            legacy.set_link_loss(a, b, pct)
        src = hosts[rng.randrange(len(hosts))]
        for dst in hosts:
            assert table.path(src, dst) == legacy.path(src, dst)
            assert table.path_latency_s(src, dst) == \
                legacy.path_latency_s(src, dst)
            r1, r2 = random.Random(1), random.Random(1)
            assert table.transfer(src, dst, 512, r1) == \
                legacy.transfer(src, dst, 512, r2)


def test_gray_loss_epoch_invalidates_keep_rows():
    """set_link_loss must repopulate composed keep values without a
    topology epoch bump (routes and tables stay valid)."""
    net = Network()
    net.add_link("a", "b", LinkCfg(lat_ms=1.0))
    net.add_link("b", "c", LinkCfg(lat_ms=1.0))
    always = random.Random(0)

    _, lost = net.transfer("a", "c", 10, always)
    epoch = net.epoch
    net.set_link_loss("a", "b", 100.0)
    assert net.epoch == epoch            # loss rides its own epoch
    _, lost = net.transfer("a", "c", 10, always)
    assert lost                          # stale keep row would say kept
    net.set_link_loss("a", "b", 0.0)
    delay, lost = net.transfer("a", "c", 10, always)
    assert not lost and delay is not None


def test_ondemand_latency_memo_pins_counters():
    """Satellite: path_latency_s memoization in on-demand mode must not
    change the fingerprinted counters — every call stays one logical
    path query, first-per-source stays one build."""
    nets = []
    for memo_on in (True, False):
        net = random_net(1, 8, False)
        net.route_mode = "ondemand"
        if not memo_on:
            net._lat_memo = _NoMemo()
        vals = [net.path_latency_s("h0", f"h{i}")
                for i in range(8) for _ in range(3)]
        nets.append((vals, net.n_path_queries, net.n_graph_builds))
    assert nets[0] == nets[1]


class _NoMemo(dict):
    def __setitem__(self, k, v):       # a memo that never retains
        pass


def test_uncached_baseline_forces_ondemand():
    """reach_cache=False is the recompute-every-query baseline in both
    route modes: identical results, one build per query."""
    net = random_net(2, 6, False)
    net.reach_cache = False
    before = net.n_graph_builds
    for _ in range(5):
        assert net.path("h0", "h1") is not None
    assert net.n_graph_builds == before + 5


# ---------------------------------------------------------------------------
# Engine-level bit-identity under chaos, across delivery modes/queue phases
# ---------------------------------------------------------------------------


CHAOS_PARAMS = {
    "topology": "geo_wan", "n_hosts": 16, "n_brokers": 3,
    "replication": 3, "n_topics": 3, "n_producers": 3,
    "rate_kbps": 16.0, "msg_size": 400, "poll_interval": 0.1,
    "loss_pct": 0.5, "chaos": 2, "horizon": 12.0, "seed": 3,
}


def run_mode(route_mode: str, delivery: str):
    params = {**CHAOS_PARAMS, "delivery": delivery,
              "route_mode": route_mode}
    spec = build_scenario(params)
    eng = Engine(spec, seed=int(params["seed"]))
    mon = eng.run(until=float(params["horizon"]))
    m = eng.metrics()
    m.pop("wall_s", None)
    deliveries = {mid: sorted(s.deliveries.items())
                  for mid, s in sorted(mon.msgs.items())}
    return m, mon.events, deliveries, eng.n_chaos_faults


@pytest.mark.parametrize("scheduler", PHASES)
@pytest.mark.parametrize("delivery", ["wakeup", "poll"])
def test_route_modes_bit_identical_under_chaos(delivery, scheduler,
                                               monkeypatch):
    queue_phase(monkeypatch, scheduler)
    table = run_mode("table", delivery)
    legacy = run_mode("ondemand", delivery)
    assert table[3] > 0, "chaos plan expanded to nothing — weak test"
    assert table[0] == legacy[0]      # every fingerprinted metric
    assert table[1] == legacy[1]      # the full monitor event log
    assert table[2] == legacy[2]      # per-message delivery times
    # the table mode must actually have been exercised
    assert table[0]["path_queries"] > 0
    assert table[0]["fault_events"] > 0


def test_node_index_matches_routing_table_order():
    """topologies.node_index is the table index space, verbatim."""
    spec = build_scenario({**CHAOS_PARAMS, "chaos": 0})
    net = spec.network
    hosts = net.hosts()
    assert net.path(hosts[0], hosts[-1]) is not None
    assert net._tables.idx == topologies.node_index(net.g)


def test_route_mode_validated_at_engine_construction():
    spec = build_scenario({**CHAOS_PARAMS, "chaos": 0})
    spec.network.route_mode = "psychic"
    with pytest.raises(ValueError, match="route_mode"):
        Engine(spec, seed=0)
