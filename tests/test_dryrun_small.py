"""End-to-end dry-run path on a small forced-device mesh (subprocess).

Validates lower→compile→memory/cost analysis→roofline on an 8-device
(2 data × 4 model) mesh with a reduced config — the same machinery the
512-device production dry-run uses, cheap enough for CI.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_config, reduce_for_smoke
    from repro.configs.base import ShapeCfg
    from repro.train import make_step_bundle
    from repro.analysis.roofline import analyze_hlo, roofline_terms
    from repro.launch.mesh import make_mesh

    cfg = reduce_for_smoke(get_config("qwen2-7b"))
    shape = ShapeCfg("t", 64, 8, "train")
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh:
        b = make_step_bundle(cfg, shape, mesh)
        jitted = jax.jit(b.step_fn, in_shardings=b.in_shardings,
                         out_shardings=b.out_shardings)
        compiled = jitted.lower(*b.in_specs).compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    a = analyze_hlo(hlo, chips_per_pod=8)
    model_flops = cfg.model_flops_per_token("train") * 8 * 64
    rl = roofline_terms(a, model_flops_total=model_flops, n_chips=8)
    print(json.dumps({
        "devices": jax.device_count(),
        "flops": a.flops,
        "hbm": a.hbm_bytes,
        "ici": a.ici_bytes,
        "collectives": len(a.collectives),
        "temp": getattr(ma, "temp_size_in_bytes", None),
        "bottleneck": rl.bottleneck,
        "useful": rl.useful_ratio,
    }))
""")


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["devices"] == 8
    assert r["flops"] > 0
    assert r["hbm"] > 0
    assert r["collectives"] > 0          # model-parallel dims communicate
    assert 0 < r["useful"] <= 2.0
