"""A Kafka cluster on one switched LAN, with the Fig. 7b app placed on it.

The cluster is Kreps' 2014 produce/consume benchmark: ``n_brokers``
brokers, each topic with ``partitions`` partitions at ``replication``,
acks=1 (asynchronous replication), one load producer of
``record_bytes`` records and one consumer of that topic.  The
traffic-monitoring app of Fig. 7b (PACKET users and a ``traffic_metrics``
stream processor, as in ``bench/kinds/star.py``) produces into and reads
from its own topic ``pkts`` on the same cluster.  Leaders of both topics
rotate over the brokers from the first.
"""
from __future__ import annotations


def build(p: dict, traffic: dict):
    """A fresh :class:`PipelineSpec` for one scenario."""
    from repro.core import PipelineSpec

    spec = PipelineSpec(mode=p["mode"], delivery=p["delivery"])
    spec.add_switch("s1")

    def host(h, lat, bw):
        spec.add_host(h).add_link(h, "s1", lat=lat, bw=bw)

    lan = (p["lan_lat_ms"], p["lan_bw_mbps"])
    brokers = [f"k{i}" for i in range(int(p["n_brokers"]))]
    for b in brokers:
        host(b, *lan)
        spec.add_broker(b)
    for t in (p["load_topic"], "pkts"):
        spec.add_topic(t, leader=brokers[0],
                       replication=int(p["replication"]),
                       partitions=int(p["partitions"]))
    rate_kbps = float(p["record_rate_rps"]) * int(p["record_bytes"]) * 8e-3
    for i in range(int(p["n_load_producers"])):
        host(f"lp{i}", *lan)
        spec.add_producer(f"lp{i}", "SYNTHETIC", topic=p["load_topic"],
                          msgSize=int(p["record_bytes"]), rateKbps=rate_kbps,
                          batchBytes=int(p["batch_bytes"]))
    for i in range(int(p["n_load_consumers"])):
        host(f"lc{i}", *lan)
        spec.add_consumer(f"lc{i}", "STANDARD", topics=[p["load_topic"]],
                          pollInterval=p["consumer_poll_s"])
    host("spark", *lan)
    spec.add_spe("spark", query="traffic_metrics", inTopic="pkts",
                 window=p["window_s"], pollInterval=p["spe_poll_s"],
                 services=list(p["services"]))
    for i in range(int(p["n_users"])):
        host(f"u{i}", p["user_lat_ms"], p["user_bw_mbps"])
        spec.add_producer(f"u{i}", "PACKET", topic="pkts",
                          ratePps=p["rate_pps"], pktBytes=p["pkt_bytes"],
                          services=list(p["services"]))
    return spec
