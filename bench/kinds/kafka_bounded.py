"""Kreps' Kafka cluster (``kafka_lan``) with load consumers that cannot
keep up.

The layout is ``bench/kinds/kafka_lan.py``'s, built by it.  Each load
consumer ``lc<i>`` then runs on ``consumer_cores`` emulated cores and
pays ``consumer_record_cost_s`` a record on top of the stub's own cost,
behind a bounded ingest queue of ``consumer_queue_bytes`` under
``consumer_shed_policy`` (``pause``: the fetch side stops while the
queue is full; nothing is dropped).
"""
from __future__ import annotations


def build(p: dict, traffic: dict):
    """A fresh :class:`PipelineSpec` for one scenario."""
    from bench import catalog

    spec = catalog.kind("kafka_lan").build(p, traffic)
    for i in range(int(p["n_load_consumers"])):
        host = spec.hosts[f"lc{i}"]
        host.n_cores = int(p["consumer_cores"])
        for comp in host.components:
            comp.cfg.update(perRecordCost=float(p["consumer_record_cost_s"]),
                            queueBytes=int(p["consumer_queue_bytes"]),
                            shedPolicy=str(p["consumer_shed_policy"]))
    return spec
