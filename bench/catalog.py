"""Everything the harness runs is found by name from ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``, via the
configuration's ``file``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration's ``kind`` names
the layout that builds it (``bench/kinds/<kind>.py``, whose
``build(params, traffic)`` returns a fresh ``PipelineSpec``).  Each
stream-processor query a scenario runs has its stage module
(``bench/stages/<query>.py``: ``warm``, ``recount``, ``gap``).  A metric
is read by ``bench/metrics/<name>.py``, whose ``read(run)`` returns a
number, or ``None`` where the run gave it nothing to read.  Adding a
cell, a configuration, a layout, a stage or a metric is adding files and
entries; no file here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The workload entry with its configuration and traffic loaded."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return {"workload": w, "config": config, "traffic": traffic}


def params(cell: dict) -> dict:
    """The deployment's parameters with the mix's overrides applied."""
    return {**cell["config"]["deployment"],
            **cell["traffic"].get("deployment", {})}


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries this cell reports in this kind of run."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif workload in e2e[m["moves"]].get("workloads", [workload]):
            out.append(m)
    return out


@functools.lru_cache(maxsize=None)
def module(group: str, name: str, root: str = ROOT):
    """``bench/<group>/<name>.py`` as a module, loaded once; ``None``
    where it is not there."""
    path = os.path.join(root, "bench", group, f"{name}.py")
    if not os.path.isfile(path):
        return None
    key = f"bench_{group}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    mod = module("metrics", name, root)
    if mod is None:
        raise KeyError(f"no reader bench/metrics/{name}.py")
    return mod.read


def kind(name: str, root: str = ROOT):
    """The layout module ``bench/kinds/<name>.py``."""
    mod = module("kinds", name, root)
    if mod is None:
        raise KeyError(f"no deployment kind bench/kinds/{name}.py")
    return mod


def stage(query: str, root: str = ROOT):
    """The stage module ``bench/stages/<query>.py``, or ``None``."""
    return module("stages", query, root)
