"""Discovery by name: a cell, a traffic mix and a metric added as files."""
import json
import os
import shutil

from bench import catalog


def test_every_cell_of_the_benchmark_resolves():
    b = catalog.load()
    for w in b["workloads"]:
        c = catalog.cell(b, w["name"])
        assert c["config"]["name"] == w["config"]
        assert c["traffic"]["scenario_s"] > 0
        assert callable(catalog.kind(c["config"]["kind"]).build)
        for m in catalog.metrics_for(b, w["name"], trace=False) + \
                catalog.metrics_for(b, w["name"], trace=True):
            assert callable(catalog.reader(m["name"]))


def test_a_cell_added_as_new_files_is_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(catalog.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = catalog.load()
    # a new configuration, traffic mix and metric, each in a file of its own
    cfg = dict(json.load(open(root / "bench/configs/fig7b_trafficmon.json")),
               name="fig7b_20u")
    cfg["deployment"]["n_users"] = 20
    (root / "bench/configs/fig7b_20u.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/short.json").write_text(json.dumps(
        {"scenario_s": 5.0, "grace_s": 1.0, "stage_buckets": [512]}))
    (root / "bench/metrics/windows_seen.py").write_text(
        "def read(run):\n    return len(run['stage_walls']) or None\n")
    b["configs"].append({"name": "fig7b_20u", "source": "x",
                         "file": "bench/configs/fig7b_20u.json",
                         "reduced": ["n_users"], "why": "x"})
    b["workloads"].append({"name": "fig7b_20u.short", "config": "fig7b_20u",
                           "traffic": "short", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "windows_seen", "unit": "windows",
                           "better": "higher", "source": "program_counter",
                           "layer": "SPE operators", "moves": "sim_rate",
                           "workloads": ["fig7b_20u.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    b2 = catalog.load(str(root))
    c = catalog.cell(b2, "fig7b_20u.short", root=str(root))
    assert c["config"]["deployment"]["n_users"] == 20
    assert c["traffic"]["scenario_s"] == 5.0
    names = [m["name"] for m in catalog.metrics_for(b2, "fig7b_20u.short",
                                                    trace=True)]
    assert names == ["windows_seen"]
    read = catalog.reader("windows_seen", root=str(root))
    assert read({"stage_walls": [0.1, 0.2]}) == 2
    # a new layout and a new stage, each a file of its own
    (root / "bench/kinds/ring.py").write_text(
        "def build(p, traffic):\n    return ('ring', p['n'])\n")
    (root / "bench/stages/wordcount.py").write_text("LIMIT = 0\n")
    assert catalog.kind("ring", root=str(root)).build({"n": 3}, {}) == \
        ("ring", 3)
    assert catalog.stage("wordcount", root=str(root)).LIMIT == 0
    assert catalog.stage("wordcount") is None
    # the cells that were there keep their metrics
    assert "windows_seen" not in [m["name"] for m in catalog.metrics_for(
        b2, "trafficmon_100u", trace=True)]


def test_metrics_without_workloads_follow_their_end_to_end_metric():
    b = {"end_to_end": [{"name": "a", "workloads": ["x"]}, {"name": "s"}],
         "per_layer": [{"name": "la", "moves": "a"},
                       {"name": "ls", "moves": "s"}]}
    assert [m["name"] for m in catalog.metrics_for(b, "x", True)] == \
        ["la", "ls"]
    assert [m["name"] for m in catalog.metrics_for(b, "y", True)] == ["ls"]
    assert [m["name"] for m in catalog.metrics_for(b, "y", False)] == ["s"]


def test_a_stage_with_no_module_or_unwarmed_shapes_is_refused(monkeypatch):
    import types

    import pytest

    from bench import run, window

    fake = types.SimpleNamespace(capture=types.SimpleNamespace(stages=[]),
                                 eng=None)
    with pytest.raises(run.Refused):
        run.warm(fake, [2048])          # shapes listed, no stage to warm
    rt = types.SimpleNamespace(comp={"services": ["a"]}, query=None)
    stage = types.SimpleNamespace(capture=types.SimpleNamespace(
        stages=[(rt, catalog.stage("traffic_metrics"), "g")]), eng=None)
    with pytest.raises(run.Refused):
        run.warm(stage, [])             # a stage, no shapes listed
    with pytest.raises(run.Refused):
        run.warm(stage, [1000])         # a length the stage never pads to
    monkeypatch.setattr(catalog, "stage", lambda q, root=None: None)
    cell = catalog.cell(catalog.load(), "trafficmon_100u")
    with pytest.raises(LookupError):
        window.Scenario(cell, 1, 0)
