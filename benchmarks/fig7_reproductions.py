"""Fig. 7: reproductions of published work inside the gym.

(a) Ichinose et al. [39]: one broker + one producer + N consumers on a
    single 8-core host; frames are produced up-front; transfer throughput
    should rise until N == cores and then flatten.
(b) Ocampo et al. [41]: broker + 1-node Spark-like SPE + N packet-
    generating users; mean *measured* execution time of the real JAX
    windowed query, normalized to 20 users, should grow with N.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, run_spec
from repro.core import PipelineSpec


def ichinose(n_consumers: int, frames: int = 1500) -> float:
    """Returns aggregate transfer throughput (bytes/s)."""
    spec = PipelineSpec()
    spec.add_switch("s1")
    # single host runs everything (paper: same server), 8 cores
    spec.add_host("srv", n_cores=8)
    spec.add_link("srv", "s1", lat=0.1, bw=10_000.0)
    spec.add_broker("srv")
    spec.add_topic("frames", leader="srv")
    spec.add_producer("srv", "FRAMES", topic="frames", count=frames,
                      frameBytes=28 * 28, burstInterval=1e-4)
    spec.hosts["srv"].components[0].cfg["fetch_bytes"] = 16 * 784
    conss = [spec.add_consumer("srv", "COUNTING", topic="frames",
                               pollInterval=0.005, perRecordCost=0.00032)
             for _ in range(n_consumers)]
    eng, mon, wall = run_spec(spec, until=120.0)
    rts = {c.name for c in conss}
    done_times = []
    total_bytes = 0
    for rt in eng.runtimes:
        if rt.name in rts and getattr(rt, "series", None):
            done_times.append(rt.series[-1][0])
            total_bytes += rt.bytes_received
    t = max(done_times) if done_times else 1.0
    return total_bytes / t


def run_ichinose() -> list[tuple[int, float]]:
    out = []
    for n in [1, 2, 4, 6, 8, 10, 12]:
        thr = ichinose(n)
        out.append((n, thr))
        emit(f"fig7a/consumers={n}", 0.0, f"throughput_Bps={thr:.0f}")
    # paper claim: grows to ~cores then flattens
    thr = dict(out)
    grows = thr[8] > 1.5 * thr[1]
    flattens = abs(thr[12] - thr[8]) < 0.35 * thr[8]
    emit("fig7a/claim", 0.0, f"grows_to_8={grows};flat_beyond_8={flattens}")
    return out


def ocampo_spec(n_users: int) -> PipelineSpec:
    """Broker + one ``traffic_metrics`` SPE (1 s window, 0.2 s poll) +
    ``n_users`` Poisson packet users at 20 pps of 256-byte packets."""
    spec = PipelineSpec()
    spec.add_switch("s1")
    spec.add_host("b").add_link("b", "s1", lat=0.5, bw=1000.0)
    spec.add_broker("b")
    spec.add_topic("pkts", leader="b")
    spec.add_host("spark").add_link("spark", "s1", lat=0.5, bw=1000.0)
    spec.add_spe("spark", query="traffic_metrics", inTopic="pkts",
                 window=1.0, pollInterval=0.2)
    for i in range(n_users):
        h = f"u{i}"
        spec.add_host(h).add_link(h, "s1", lat=0.5, bw=100.0)
        spec.add_producer(h, "PACKET", topic="pkts", ratePps=20.0,
                          pktBytes=256)
    return spec


def ocampo(n_users: int, horizon: float = 30.0) -> float:
    """Returns mean measured SPE execution wall time (s)."""
    eng, mon, wall = run_spec(ocampo_spec(n_users), until=horizon,
                              seed=n_users)
    walls = [e["wall"] for e in mon.events_of("spe_exec")]
    assert walls, "SPE executed no windows"
    return float(np.mean(walls[2:])) if len(walls) > 4 else float(
        np.mean(walls))


def run_ocampo() -> list[tuple[int, float]]:
    users = [20, 40, 60, 80, 100]
    raw = [(n, ocampo(n)) for n in users]
    base = raw[0][1]
    out = [(n, w / base) for n, w in raw]
    for n, norm in out:
        emit(f"fig7b/users={n}", raw[[u for u, _ in raw].index(n)][1] * 1e6,
             f"normalized_exec_time={norm:.3f}")
    emit("fig7b/claim", 0.0,
         f"monotonic_growth={out[-1][1] > out[0][1]}")
    return out


def run() -> dict:
    return {"ichinose": run_ichinose(), "ocampo": run_ocampo()}


if __name__ == "__main__":
    print(run())
