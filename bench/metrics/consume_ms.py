"""Self wall ms per emulated second of ``consume``:
a consumer's completion work (engine profiler, bench/hostprof.py)."""
from bench.hostprof import layer_ms_per_sim_s

BUCKETS = ('consume',)


def read(run):
    per = layer_ms_per_sim_s(run["host"], run["host_sim_s"])
    got = [per[b] for b in BUCKETS if b in per]
    return sum(got) if got else None
