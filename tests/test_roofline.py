"""HLO roofline parser: trip counts, dot FLOPs, collective classification."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import (
    analyze_hlo, computation_multipliers, parse_hlo, roofline_terms,
    _parse_groups,
)


def test_scan_trip_count_multiplies_flops():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    a = analyze_hlo(comp.as_text())
    analytic = 2 * 128 * 256 * 256 * 10
    assert a.flops == pytest.approx(analytic, rel=0.01)


def test_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(d, _):
                return d @ d, None
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(f).lower(x).compile()
    a = analyze_hlo(comp.as_text())
    analytic = 2 * 64 ** 3 * 15
    assert a.flops == pytest.approx(analytic, rel=0.01)


def test_batched_dot_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    comp = jax.jit(f).lower(a, b).compile()
    an = analyze_hlo(comp.as_text())
    assert an.flops == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.01)


def test_hbm_bytes_reasonable_for_elementwise():
    """A big elementwise chain must count ~2 tensor-touches, not 10."""
    def f(x):
        for _ in range(10):
            x = jnp.tanh(x) * 1.5 + 0.5
        return x

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    comp = jax.jit(f).lower(x).compile()
    a = analyze_hlo(comp.as_text())
    nbytes = 1024 * 1024 * 4
    assert a.hbm_bytes <= 6 * nbytes     # fused: far less than 20 touches


def test_replica_group_brace_and_iota():
    g = _parse_groups("replica_groups={{0,1},{2,3}}")
    np.testing.assert_array_equal(g, [[0, 1], [2, 3]])
    g = _parse_groups("replica_groups=[2,2]<=[4]")
    np.testing.assert_array_equal(g, [[0, 1], [2, 3]])
    g = _parse_groups("replica_groups=[2,2]<=[2,2]T(1,0)")
    np.testing.assert_array_equal(g, [[0, 2], [1, 3]])


def test_collective_pod_classification():
    """Synthetic HLO: a group spanning ids 0/256 is DCN; 0..15 is ICI."""
    hlo = """
HloModule m

ENTRY %main (p: f32[256,16]) -> f32[256,16] {
  %p = f32[256,16] parameter(0)
  %ar0 = f32[256,16] all-reduce(%p), replica_groups=[32,16]<=[512]
  ROOT %ar1 = f32[256,16] all-reduce(%ar0), replica_groups=[256,2]<=[2,256]T(1,0)
}
"""
    a = analyze_hlo(hlo, chips_per_pod=256)
    assert a.ici_bytes > 0 and a.dcn_bytes > 0
    kinds = {(c.kind, c.crosses_pod) for c in a.collectives}
    assert ("all-reduce", False) in kinds and ("all-reduce", True) in kinds


def test_roofline_terms_and_bottleneck():
    from repro.analysis.peaks import peaks_for
    from repro.analysis.roofline import HLOAnalysis
    a = HLOAnalysis(flops=197e12, hbm_bytes=819e9 / 2, ici_bytes=0,
                    dcn_bytes=0)
    r = roofline_terms(a, model_flops_total=197e12 * 256, n_chips=256)
    assert r.bottleneck == "compute"
    assert r.t_compute == pytest.approx(1.0)
    assert r.useful_ratio == pytest.approx(1.0)
    assert r.roofline_fraction == pytest.approx(1.0)


def test_roofline_terms_name_their_device_and_refuse_unknown_kinds():
    from repro.analysis.peaks import peaks_for
    from repro.analysis.roofline import HLOAnalysis
    a = HLOAnalysis(flops=1e12, hbm_bytes=1e9)
    r = roofline_terms(a, model_flops_total=1e12, n_chips=1)
    assert r.device_kind == "TPU v5 lite"
    assert r.to_json()["device_kind"] == "TPU v5 lite"
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_all_baseline_cells_present_and_ok():
    """The 40-cell × 2-mesh dry-run artifact set is complete."""
    import glob
    import json
    import os
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "dryrun")
    if not os.path.isdir(out):
        pytest.skip("dry-run artifacts not generated yet")
    from repro.configs import SHAPES, list_configs
    missing, failed = [], []
    for mesh in ("16x16", "2x16x16"):
        for arch in list_configs():
            for shape in SHAPES:
                p = os.path.join(out, f"{arch}__{shape}__{mesh}.json")
                if not os.path.exists(p):
                    missing.append((arch, shape, mesh))
                    continue
                r = json.load(open(p))
                if r["status"] == "FAIL":
                    failed.append((arch, shape, mesh))
    assert not missing, f"missing cells: {missing[:5]}"
    assert not failed, f"failed cells: {failed[:5]}"
