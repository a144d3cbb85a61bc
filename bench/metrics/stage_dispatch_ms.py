"""Self wall ms per emulated second of ``stage_dispatch``:
a stage call's later calls of its jitted function (engine profiler,
bench/hostprof.py)."""
from bench.hostprof import layer_ms_per_sim_s

BUCKETS = ('stage_dispatch',)


def read(run):
    per = layer_ms_per_sim_s(run["host"], run["host_sim_s"])
    got = [per[b] for b in BUCKETS if b in per]
    return sum(got) if got else None
