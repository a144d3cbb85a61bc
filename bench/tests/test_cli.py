"""The command refuses, with no result, where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

from bench import catalog

CMD = [sys.executable, "bench/run.py", "--workload", "trafficmon_100u",
       "--seed", "3000000019", "--seconds", "1", "--trace", "0"]


def _run(cwd, cmd=CMD):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(catalog.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = _run(catalog.ROOT, CMD[:3] + ["nope"] + CMD[4:])
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(catalog.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(catalog.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
    for line in p.stdout.splitlines():
        json.loads(line)        # nothing printed that looks like a result
