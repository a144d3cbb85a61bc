"""Produce batcher: the row-by-row replica append, linger/batch_bytes
flush semantics, one ack/retry timer per batch, and delivery equivalence
with the legacy per-record path.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import Engine, PipelineSpec, RecordBatch
from repro.core.broker import Record, ReplicaLog


# ---------------------------------------------------------------------------
# ReplicaLog.append_batch
# ---------------------------------------------------------------------------


def test_replica_log_append_batch_stamps_offsets_and_epoch():
    rl = ReplicaLog("t", partition=2)
    recs = [Record(i + 1, "t", f"v{i}", 10, 0.0, "p", partition=2,
                   key="k") for i in range(4)]
    out = rl.append_batch(recs, epoch=7)
    assert [r.offset for r in out] == [0, 1, 2, 3]
    assert all(r.epoch == 7 for r in out)
    assert rl.leo == 4
    assert all(r.partition == 2 for r in rl.records)
    assert all(r.key == "k" for r in rl.records)


def _records(k, first_id):
    # sizes alternate python and numpy ints, epochs differ per record and
    # event times differ from produce times, so every column is exercised
    return [Record(first_id + i, "t", {"i": i}, (np.int64 if i % 2 else int)
                   (10 + 3 * i), 0.25 * i, f"p{i % 3}", epoch=i % 4,
                   partition=1, key=f"k{i % 5}" if i % 2 else None,
                   event_time=0.25 * i - 0.1)
            for i in range(k)]


@pytest.mark.parametrize("prefill", [0, 63])
@pytest.mark.parametrize("epoch", [7, None])
@pytest.mark.parametrize("k", [1, 2, 3, 63, 64, 65, 129])
def test_append_batch_matches_a_plain_reference(k, epoch, prefill):
    """The log and the copies ``append_batch`` leaves equal ones built
    in bulk from the same records, across the growth boundaries."""
    rl = ReplicaLog("t", partition=1)
    for i in range(prefill):
        rl.batch.append_row(i, 7, 0.5, 0, i, "q")
    recs = _records(k, first_id=1000)
    out = rl.append_batch(recs, epoch=epoch)
    ref = [dataclasses.replace(r, offset=prefill + j,
                               epoch=r.epoch if epoch is None else epoch)
           for j, r in enumerate(recs)]
    assert out == ref
    assert all(o is not r for o, r in zip(out, recs))     # copies
    assert rl.records[prefill:] == ref

    b, n = rl.batch, prefill + k
    sizes = [7] * prefill + [int(r.size) for r in recs]
    want = {
        "msg_id": list(range(prefill)) + [r.msg_id for r in recs],
        "size": sizes,
        "produce_time": [0.5] * prefill + [r.produce_time for r in recs],
        "epoch": [0] * prefill + [r.epoch for r in ref],
        "event_time": [0.5] * prefill + [r.event_time for r in recs],
        "cum_size": np.cumsum(sizes).tolist(),
    }
    cap = RecordBatch._MIN_CAP
    while cap < n:
        cap *= 2                                       # doubling growth
    fresh = RecordBatch()
    assert b.n == n
    for col, values in want.items():
        x = getattr(b, col)
        assert len(x) == cap, col
        assert x.dtype == getattr(fresh, col).dtype, col
        assert np.array_equal(x[:n], np.asarray(values, x.dtype)), col
    assert b.cum_list == want["cum_size"]
    assert all(type(c) is int for c in b.cum_list)
    assert b.payloads == list(range(prefill)) + [r.payload for r in recs]
    assert b.producers == ["q"] * prefill + [r.producer for r in recs]
    assert b.keys == [None] * prefill + [r.key for r in recs]


def test_stamped_copy_carries_every_record_field():
    # a distinct value per field, so a field the copy drops or swaps shows
    vals = {f.name: object() for f in dataclasses.fields(Record)}
    rec = Record(**vals)
    out = rec.stamped(offset=5, epoch=9)
    assert out is not rec
    assert out == dataclasses.replace(rec, offset=5, epoch=9)


# ---------------------------------------------------------------------------
# End-to-end linger behavior
# ---------------------------------------------------------------------------


def batch_spec(*, linger_ms=0.0, batch_bytes=1 << 14, total=60,
               rate_kbps=200.0, fault=None, mode="zk"):
    spec = PipelineSpec(mode=mode)
    spec.add_switch("s1")
    spec.add_host("b1").add_link("b1", "s1", lat=1.0, bw=100.0)
    spec.add_broker("b1")
    spec.add_topic("t", leader="b1")
    spec.add_host("p").add_link("p", "s1", lat=1.0, bw=100.0)
    # 200 kbps / 500 B -> one record every 20 ms
    spec.add_producer("p", "SYNTHETIC", topics=["t"], rateKbps=rate_kbps,
                      msgSize=500, totalMessages=total,
                      lingerMs=linger_ms, batchBytes=batch_bytes)
    spec.add_host("c").add_link("c", "s1", lat=1.0, bw=100.0)
    spec.add_consumer("c", "STANDARD", topics=["t"], pollInterval=0.1)
    if fault:
        spec.add_fault(*fault[0], **fault[1])
    return spec


def run_metrics(spec, seed=0, until=30.0):
    eng = Engine(spec, seed=seed)
    mon = eng.run(until=until)
    return eng, mon


def delivered_set(mon):
    return sorted((mid, c) for mid, m in mon.msgs.items()
                  for c in m.deliveries)


def test_linger_zero_is_one_batch_per_record():
    eng, mon = run_metrics(batch_spec(linger_ms=0.0))
    assert eng.cluster.n_produce_batches == len(mon.msgs) == 60


def test_linger_accumulates_and_preserves_delivery_set():
    eng0, mon0 = run_metrics(batch_spec(linger_ms=0.0))
    eng1, mon1 = run_metrics(batch_spec(linger_ms=100.0))
    # ~5 records per 100 ms linger at one record / 20 ms
    assert eng1.cluster.n_produce_batches * 4 <= \
        eng0.cluster.n_produce_batches
    assert delivered_set(mon0) == delivered_set(mon1)
    assert len(delivered_set(mon1)) == 60
    # batching cuts the produce-side event count too
    assert eng1.n_events < eng0.n_events
    # produce_time is stamped at produce() call, not at flush
    times0 = sorted(m.produce_time for m in mon0.msgs.values())
    times1 = sorted(m.produce_time for m in mon1.msgs.values())
    assert times0 == times1


def test_batch_bytes_flushes_before_linger():
    # batch.size = 2 records; a huge linger must not delay the flush
    eng, mon = run_metrics(batch_spec(linger_ms=60_000.0,
                                      batch_bytes=1000))
    assert eng.cluster.n_produce_batches == 30      # 60 records / 2
    assert len(delivered_set(mon)) == 60


def test_batch_retries_as_one_unit_through_fault():
    # broker unreachable for a window: flushed batches buffer + retry
    # (one retry timer per batch), then deliver after the heal — nothing
    # expires, nothing is delivered twice
    fault = ((5.0, "link_down", "b1", "s1"), {"duration": 10.0})
    eng, mon = run_metrics(
        batch_spec(linger_ms=100.0, total=100, fault=fault), until=80.0)
    m = eng.metrics()
    assert m["records_expired"] == 0
    assert m["records_produced"] == 100
    assert m["records_delivered"] == 100
    assert max(len(s.deliveries) for s in mon.msgs.values()) == 1
    assert m["produce_batches"] < 60    # retries never re-count a batch


def test_metrics_produce_batches_is_deterministic():
    runs = [run_metrics(batch_spec(linger_ms=100.0), seed=5)[0]
            .metrics()["produce_batches"] for _ in range(2)]
    assert runs[0] == runs[1]


def test_retried_batches_keep_partition_log_in_produce_order():
    # idempotent-producer sequencing: while the partition leader is
    # unreachable, flushed batches queue FIFO behind one in-flight head;
    # after failover they land in produce order — the log (and hence
    # per-key delivery) is never reordered by independent retry timers
    spec = PipelineSpec(mode="zk")
    spec.add_switch("s1")
    for b in ("b1", "b2", "b3"):
        spec.add_host(b).add_link(b, "s1", lat=1.0, bw=100.0)
        spec.add_broker(b)
    spec.add_topic("t", leader="b1", replication=3, partitions=2)
    spec.add_host("p").add_link("p", "s1", lat=1.0, bw=100.0)
    spec.add_producer("p", "SYNTHETIC", topics=["t"], rateKbps=60.0,
                      msgSize=500, totalMessages=200, nKeys=4,
                      lingerMs=80.0)
    spec.add_host("c").add_link("c", "s1", lat=1.0, bw=100.0)
    spec.add_consumer("c", "STANDARD", topics=["t"], pollInterval=0.2)
    spec.add_fault(5.0, "link_down", "b1", "s1", duration=12.0)
    eng = Engine(spec, seed=3)
    mon = eng.run(until=60.0)
    assert eng.metrics()["elections"] >= 1, "failover must happen"
    for p, pm in enumerate(eng.cluster.topics["t"].parts):
        log = eng.cluster.logs[pm.leader].get(("t", p))
        pts = list(log.batch.produce_time[:log.leo])
        assert pts == sorted(pts), f"partition {p} reordered by retries"
    per = {}
    for m in sorted(mon.msgs.values(), key=lambda s: s.produce_time):
        for c, t in m.deliveries.items():
            per.setdefault((c, m.partition), []).append(t)
    assert per and all(v == sorted(v) for v in per.values())
