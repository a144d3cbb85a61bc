"""Self wall ms per emulated second of ``stage_compile``:
a stage call's first call of a jitted function new to its query
instance: trace, then compile or cache load (engine profiler,
bench/hostprof.py)."""
from bench.hostprof import layer_ms_per_sim_s

BUCKETS = ('stage_compile',)


def read(run):
    per = layer_ms_per_sim_s(run["host"], run["host_sim_s"])
    got = [per[b] for b in BUCKETS if b in per]
    return sum(got) if got else None
