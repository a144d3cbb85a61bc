"""The Kreps cluster with a consumer that cannot keep up
(``kafka_bounded``) and its reader."""
import json
import os

from bench import catalog


def _cell():
    return catalog.cell(catalog.load(), "kreps2014_overload")


def test_kafka_bounded_builds_kafka_lan_with_a_slow_bounded_consumer():
    from repro.core.spec import CONSUMER, PRODUCER, SPE

    c = _cell()
    p = catalog.params(c)
    spec = catalog.kind(c["config"]["kind"]).build(p, c["traffic"])
    base = catalog.kind("kafka_lan").build(p, c["traffic"])
    # the same cluster and app: hosts, links, topics, producers, the SPE
    assert sorted(spec.hosts) == sorted(base.hosts)
    assert {t: (v.partitions, v.replication) for t, v in spec.topics.items()} \
        == {"test": (6, 3), "pkts": (6, 3)}
    assert len(spec.components(PRODUCER)) == 101
    assert len(spec.components(SPE)) == 1
    # one core on the load consumer's host, every other host as built
    assert spec.hosts["lc0"].n_cores == 1
    assert {h.n_cores for n, h in spec.hosts.items() if n != "lc0"} == \
        {base.hosts["lc0"].n_cores}
    (lc,) = spec.components(CONSUMER)
    assert lc.get("topics") == ["test"]
    assert lc.get("perRecordCost") == 0.0011998
    assert lc.get("queueBytes") == 50_000
    assert lc.get("shedPolicy") == "pause"
    assert lc.get("pollInterval") == 0.1
    assert not spec.faults


def test_lan_overload_checks_what_its_grace_leaves():
    c = _cell()
    t = c["traffic"]
    assert t["scenario_s"] == 20.0 and t["grace_s"] == 5.0
    assert t["stage_buckets"] == [2048, 4096]
    assert c["config"]["reduced"] == {
        "record_rate_rps": json.load(open(os.path.join(
            catalog.ROOT, "bench", "configs",
            "kreps2014_trafficmon.json")))["reduced"]["record_rate_rps"]}


def test_backpressure_reader_is_silent_without_its_span():
    read = catalog.reader("backpressure_ms")
    assert read({"host": {"consume": 0.01, "event_fn": 1.0},
                 "host_sim_s": 20.0}) is None
    assert read({"host": {"backpressure": 0.002, "event_fn": 1.0},
                 "host_sim_s": 20.0}) == 0.1
