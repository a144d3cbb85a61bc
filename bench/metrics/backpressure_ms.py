"""Self wall ms per emulated second of ``backpressure``: a bounded
subscriber's budget split, pauses, drains and resumes (engine profiler,
bench/hostprof.py).  ``None`` where the program has no such span."""
from bench.hostprof import layer_ms_per_sim_s

BUCKETS = ('backpressure',)


def read(run):
    per = layer_ms_per_sim_s(run["host"], run["host_sim_s"])
    got = [per[b] for b in BUCKETS if b in per]
    return sum(got) if got else None
