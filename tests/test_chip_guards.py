"""One process per chip, no CPU fallback, and where compiled code is kept.

- ``chip_smoke.py`` refuses to run without a TPU, and without the
  repository around it, before any phase starts.
- A sweep scenario with a JAX stage never runs in a pool worker: the
  worker refuses before importing JAX and the owning process runs it.
- :func:`repro.chip.init_jax` keeps the compile cache where
  ``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro import chip
from repro.core import PipelineSpec
from repro.sweep import SweepSpec, run_sweep, shutdown_pool, warm_pool
from repro.sweep.runner import _run_one, _worker_probe

# no module-level JAX import: pool workers import this module to unpickle
# the builder below, and must stay JAX-free
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _python(args, cwd, **env_over):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    out = _python(["chip_smoke.py"], ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""          # no phase ran, no ok line


def test_chip_smoke_refuses_without_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _python(["chip_smoke.py"], str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def packet_pipeline(params):
    """Two packet users into one SPE: ``traffic_metrics`` runs JAX,
    ``identity`` does not."""
    spec = PipelineSpec()
    spec.add_switch("s1")
    for h in ("b", "spe", "u0", "u1"):
        spec.add_host(h).add_link(h, "s1", lat=0.5, bw=100.0)
    spec.add_broker("b")
    spec.add_topic("pkts", leader="b")
    spec.add_spe("spe", query=params["query"], inTopic="pkts", window=1.0,
                 pollInterval=0.2)
    for u in ("u0", "u1"):
        spec.add_producer(u, "PACKET", topic="pkts", ratePps=20.0)
    return spec


@pytest.fixture
def fresh_pool():
    shutdown_pool()
    yield
    shutdown_pool()


def test_jax_stage_scenarios_run_only_in_the_owning_process(fresh_pool):
    sweep = SweepSpec(name="chip_owner",
                      axes={"query": ["traffic_metrics", "identity"],
                            "seed": [0, 1]},
                      base={"horizon": 3.0}, builder=packet_pipeline)
    res = run_sweep(sweep, workers=2, cache_dir=None)
    assert len(res) == 4
    assert all(r["metrics"]["records_produced"] > 0 for r in res.rows)
    probes = warm_pool(2).map(_worker_probe, range(8))
    assert probes and not any(p["jax_loaded"] for p in probes)
    # the same rows as a single-process run
    assert res.fingerprint() == run_sweep(sweep, workers=1).fingerprint()


def test_pool_worker_refuses_a_jax_stage(fresh_pool):
    params = {"query": "traffic_metrics", "horizon": 2.0, "seed": 0}
    task = ("sid", params, packet_pipeline, 1, None)
    with pytest.raises(chip.JaxForbidden, match="sweep pool worker"):
        warm_pool(1).apply(_run_one, (task,))
    assert not warm_pool(1).apply(_worker_probe)["jax_loaded"]


def test_every_lazy_jax_stage_passes_the_gate(monkeypatch):
    """Operator and kernel-backend JAX paths refuse where JAX is forbidden,
    not only the query classes."""
    import numpy as np

    from repro.core.operators import WindowAggregate
    from repro.kernels import cohort, netcalc

    monkeypatch.setattr(chip, "_forbidden_in", "sweep pool worker")
    stages = [lambda: WindowAggregate("sum")._reduce_fn(16),
              lambda: cohort._pane_starts_jax([0.5], 1.0),
              lambda: netcalc._delay_many_jax(np.zeros(1), np.ones(1), 8,
                                              None)]
    for build in stages:
        with pytest.raises(chip.JaxForbidden, match="sweep pool worker"):
            build()


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    script = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, "src")
        from repro import chip
        path = chip.init_jax()
        import jax, jax.numpy as jnp
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
        print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
    """)
    out = _python(["-c", script], ROOT, JAX_COMPILATION_CACHE_DIR=str(cache),
                  JAX_ENABLE_COMPILATION_CACHE="true")
    assert out.returncode == 0, out.stderr[-2000:]
    path, configured = json.loads(out.stdout.strip().splitlines()[-1])
    assert path == configured == str(cache)
    assert os.listdir(cache)                 # the program was cached there


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    monkeypatch.delenv(chip.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = chip.init_jax()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
