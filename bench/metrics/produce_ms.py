"""Self wall ms per emulated second of ``produce``:
a producer's send, or a send made outside one (retry, queued batch,
linger flush): routing, the record, batching, the first send (engine
profiler, bench/hostprof.py)."""
from bench.hostprof import layer_ms_per_sim_s

BUCKETS = ('produce',)


def read(run):
    per = layer_ms_per_sim_s(run["host"], run["host_sim_s"])
    got = [per[b] for b in BUCKETS if b in per]
    return sum(got) if got else None
