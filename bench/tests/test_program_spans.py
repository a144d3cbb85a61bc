"""Readers of the program's produce-path and stage-part spans, and the
stage call's annotations in a device trace."""
import pytest

from bench import catalog
from bench.hostprof import Recorder, merge

# metric -> the profiler bucket it reads
READERS = {
    "produce_ms": "produce", "append_ms": "append",
    "replicate_ms": "replicate", "consume_ms": "consume",
    "stage_build_ms": "stage_build", "stage_compile_ms": "stage_compile",
    "stage_dispatch_ms": "stage_dispatch", "stage_wait_ms": "stage_wait",
    "stage_readback_ms": "stage_readback",
}
PARTS = ("stage_build", "stage_compile", "stage_wait", "stage_readback")


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _nested_run(sim_s=4.0) -> dict:
    """A recorder fed as the program feeds it, on a fake clock.

    produce [0, 3] holds netem_path [1, 2]; append [4, 10] holds
    replicate [6, 9], which holds netem_path [7, 8]; a follower's
    replicate [11, 12]; consume [13, 14]; operator [20, 30] holds
    stage_build [21, 23], stage_compile [23, 26], stage_wait [26, 27]
    and stage_readback [27, 29]."""
    clk = FakeClock()
    r = Recorder(clock=clk)

    def close(phase, start, end):
        clk.t = end
        r.add(phase, end - start)

    close("netem_path", 1, 2)
    close("produce", 0, 3)
    close("netem_path", 7, 8)
    close("replicate", 6, 9)
    close("append", 4, 10)
    close("replicate", 11, 12)
    close("consume", 13, 14)
    for phase, a, b in (("stage_build", 21, 23), ("stage_compile", 23, 26),
                        ("stage_wait", 26, 27), ("stage_readback", 27, 29)):
        close(phase, a, b)
    close("operator", 20, 30)
    r.add_wall("event_fn", 40.0)
    r.add_wall("scheduler_pop", 2.0)
    return {"host": merge([r]), "host_sim_s": sim_s}


# self seconds of each bucket in _nested_run
SELF_S = {"produce": 2.0, "append": 3.0, "replicate": 3.0, "consume": 1.0,
          "stage_build": 2.0, "stage_compile": 3.0, "stage_wait": 1.0,
          "stage_readback": 2.0, "operator": 2.0}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_bucket_self_time(metric):
    read = catalog.reader(metric)
    bucket = READERS[metric]
    run = _nested_run(sim_s=4.0)
    got = read(run)
    if bucket == "stage_dispatch":       # absent from this run
        assert got is None
    else:
        assert got == pytest.approx(1e3 * SELF_S[bucket] / 4.0)
    # and nothing where the program recorded no such bucket
    empty = {"host": {"event_fn": 1.0, "scheduler_pop": 0.1},
             "host_sim_s": 4.0}
    assert read(empty) is None


def test_operator_keeps_only_what_its_parts_leave():
    run = _nested_run(sim_s=4.0)
    assert catalog.reader("operator_ms")(run) == pytest.approx(1e3 * 2 / 4)
    # the remainder shrinks by every named span
    named = sum(SELF_S.values()) + 2.0        # + the two netem_path spans
    assert catalog.reader("unattributed_ms")(run) == \
        pytest.approx(1e3 * (40.0 - named) / 4.0)


def test_new_metrics_are_listed_with_readers():
    b = catalog.load()
    per = {m["name"]: m for m in b["per_layer"]}
    for name in READERS:
        m = per[name]
        assert m["source"] == "program_span" and m["moves"] == "sim_rate"
        assert callable(catalog.reader(name))
    assert per["replicate_ms"]["workloads"] == ["kreps2014_rep3"]
    assert per["consume_ms"]["workloads"] == ["kreps2014_rep3"]


def test_stage_call_annotations_in_the_device_trace(tmp_path):
    """One stage call through the runtime, under the JAX profiler: the
    program's ``operator`` annotation holds the stage parts, in order."""
    from jax import profiler

    from bench import trace as tr
    from repro.core import Engine, PipelineSpec
    from repro.core.operators import Element
    from repro.core.spe import SPERuntime

    spec = PipelineSpec(mode="zk", delivery="wakeup")
    spec.add_switch("s1")
    for h in ("b", "spark"):
        spec.add_host(h).add_link(h, "s1", lat=0.5, bw=1000.0)
    spec.add_broker("b")
    spec.add_topic("pkts", leader="b")
    spec.add_spe("spark", query="traffic_metrics", inTopic="pkts",
                 window=1.0)
    eng = Engine(spec, seed=0)
    eng.profiler = rec = Recorder()
    rt = next(r for r in eng.runtimes if isinstance(r, SPERuntime))
    rows = [Element({"service": "dns", "bytes": 5}, 5, 0.0, None)
            for _ in range(40)]
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    profiler.start_trace(str(tmp_path), profiler_options=opts)
    rt._process(eng, rows)
    profiler.stop_trace()
    names = ("operator",) + PARTS + ("stage_dispatch",)
    host = tr.read(str(tmp_path), annotations=names)["host"]
    assert host["stage_dispatch"] == []        # a first call compiles
    (op,) = host["operator"]
    spans = [host[p] for p in PARTS]
    assert all(len(s) == 1 for s in spans)
    spans = [s[0] for s in spans]
    assert op[0] <= spans[0][0]
    for (_, end), (start, _) in zip(spans, spans[1:]):
        assert end <= start                    # in order, apart
    assert spans[-1][1] <= op[1]
    # the recorder took the same calls as the annotations
    assert {p: rec.counts[p] for p in PARTS} == dict.fromkeys(PARTS, 1)
    assert rec.counts["operator"] == 1
