"""Each layout builds the deployment its configuration file states."""
import json
import os

from bench import catalog


def _cell(config):
    cfg = json.load(open(os.path.join(catalog.ROOT, "bench", "configs",
                                      f"{config}.json")))
    return {"config": cfg, "traffic": {"scenario_s": 20.0}}


def _build(config):
    c = _cell(config)
    return catalog.kind(c["config"]["kind"]).build(catalog.params(c),
                                                   c["traffic"])


def test_star_holds_the_fig7b_deployment():
    from repro.core.spec import BROKER, PRODUCER, SPE

    spec = _build("fig7b_trafficmon")
    assert len(spec.hosts) == 102 and spec.broker_hosts() == ["b"]
    assert len(spec.components(BROKER)) == 1
    users = spec.components(PRODUCER)
    assert len(users) == 100
    assert {(c.type, c.get("ratePps"), c.get("pktBytes")) for c in users} \
        == {("PACKET", 20.0, 256)}
    (spe,) = spec.components(SPE)
    assert (spe.get("query"), spe.get("window"), spe.get("pollInterval")) \
        == ("traffic_metrics", 1.0, 0.2)
    assert spec.network.link("u0", "s1").lat_ms == 0.5
    assert spec.network.link("u0", "s1").bw_mbps == 100.0
    assert spec.network.link("b", "s1").bw_mbps == 1000.0


def test_kafka_lan_holds_the_kreps_cluster_and_the_app():
    from repro.core.spec import BROKER, CONSUMER, PRODUCER, SPE

    spec = _build("kreps2014_trafficmon")
    assert spec.broker_hosts() == ["k0", "k1", "k2"]
    assert len(spec.components(BROKER)) == 3
    for t in ("test", "pkts"):
        cfg = spec.topics[t]
        assert (cfg.partitions, cfg.replication, cfg.leader) == (6, 3, "k0")
    load = [c for c in spec.components(PRODUCER) if c.type == "SYNTHETIC"]
    (lp,) = load
    assert (lp.get("msgSize"), lp.get("batchBytes")) == (100, 8196)
    # 1,000 records a second of 100 B
    assert lp.get("rateKbps") == 800.0
    (lc,) = spec.components(CONSUMER)
    assert lc.get("topics") == ["test"]
    users = [c for c in spec.components(PRODUCER) if c.type == "PACKET"]
    assert len(users) == 100
    (spe,) = spec.components(SPE)
    assert (spe.get("inTopic"), spe.get("window")) == ("pkts", 1.0)
    assert spec.network.link("k1", "s1").bw_mbps == 1000.0
    assert spec.network.link("k1", "s1").lat_ms == 0.1
    assert not spec.faults


def test_every_configuration_names_a_layout_that_exists():
    for c in catalog.load()["configs"]:
        cfg = json.load(open(os.path.join(catalog.ROOT, c["file"])))
        assert callable(catalog.kind(cfg["kind"]).build)


def test_scenario_seeds_differ_and_fit_large_run_seeds():
    from bench import window

    s = {window.scenario_seed(2**31 + 5, i) for i in range(50)}
    assert len(s) == 50 and all(0 <= x < 2**62 for x in s)
    assert window.scenario_seed(7, 0) == window.scenario_seed(7, 0)
