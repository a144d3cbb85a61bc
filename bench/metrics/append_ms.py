"""Self wall ms per emulated second of ``append``:
the leader's log append, ack and high-watermark commit (engine profiler,
bench/hostprof.py)."""
from bench.hostprof import layer_ms_per_sim_s

BUCKETS = ('append',)


def read(run):
    per = layer_ms_per_sim_s(run["host"], run["host_sim_s"])
    got = [per[b] for b in BUCKETS if b in per]
    return sum(got) if got else None
