"""The Fig. 7b traffic-monitoring pipeline on a star.

One broker and one ``traffic_metrics`` stream processor on a switch,
with ``n_users`` PACKET users; copied from ``ocampo_spec`` in
``benchmarks/fig7_reproductions.py``.
"""
from __future__ import annotations


def build(p: dict, traffic: dict):
    """A fresh :class:`PipelineSpec` for one scenario."""
    from repro.core import PipelineSpec

    spec = PipelineSpec(mode=p["mode"], delivery=p["delivery"])
    spec.add_switch("s1")
    spec.add_host("b").add_link("b", "s1", lat=p["core_lat_ms"],
                                bw=p["core_bw_mbps"])
    spec.add_broker("b")
    spec.add_topic("pkts", leader="b")
    spec.add_host("spark").add_link("spark", "s1", lat=p["core_lat_ms"],
                                    bw=p["core_bw_mbps"])
    spec.add_spe("spark", query="traffic_metrics", inTopic="pkts",
                 window=p["window_s"], pollInterval=p["spe_poll_s"],
                 services=list(p["services"]))
    for i in range(int(p["n_users"])):
        h = f"u{i}"
        spec.add_host(h).add_link(h, "s1", lat=p["user_lat_ms"],
                                  bw=p["user_bw_mbps"])
        spec.add_producer(h, "PACKET", topic="pkts", ratePps=p["rate_pps"],
                          pktBytes=p["pkt_bytes"],
                          services=list(p["services"]))
    return spec
