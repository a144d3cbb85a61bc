"""Optimizer, schedule, and gradient-compression tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.optim import (
    AdamW, OptConfig, clip_by_global_norm, cosine_warmup, dequantize_int8,
    ef_init, global_norm, quantize_int8,
)


def test_adamw_converges_quadratic():
    opt = AdamW(OptConfig(lr=0.1, weight_decay=0.0))
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    assert float(loss(params)) < 1e-3


def test_adamw_bf16_state_dtype():
    opt = AdamW(OptConfig(state_dtype="bfloat16"))
    params = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
    state = opt.init(params)
    assert state["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    params2, state2 = opt.update(g, state, params)
    assert params2["w"].dtype == jnp.bfloat16
    assert int(state2["step"]) == 1


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(90 + 160))
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    same, _ = clip_by_global_norm(tree, 1e9)
    np.testing.assert_allclose(np.asarray(same["a"]), 3.0)


def test_cosine_warmup_shape():
    lr = cosine_warmup(1.0, 100, 1000, min_ratio=0.1)
    assert float(lr(0)) == 0.0
    assert float(lr(50)) == pytest.approx(0.5)
    assert float(lr(100)) == pytest.approx(1.0)
    assert float(lr(1000)) == pytest.approx(0.1, abs=1e-6)
    assert float(lr(550)) < float(lr(150))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_quantize_roundtrip_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, rng.uniform(0.01, 10), 128), jnp.float32)
    q, scale = quantize_int8(x)
    back = dequantize_int8(q, scale)
    # error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) * 0.5 + 1e-7


def test_error_feedback_unbiased_over_time():
    """With EF, the *accumulated* compressed sum tracks the true sum."""
    rng = np.random.default_rng(0)
    true_sum = np.zeros(64)
    comp_sum = np.zeros(64)
    e = jnp.zeros(64)
    for _ in range(200):
        g = jnp.asarray(rng.normal(0, 1, 64), jnp.float32)
        true_sum += np.asarray(g)
        q, s = quantize_int8(g + e)
        deq = dequantize_int8(q, s)
        e = g + e - deq
        comp_sum += np.asarray(deq)
    # residual error is bounded by the last step's quantization error,
    # not growing with T
    assert np.max(np.abs(true_sum - comp_sum)) <= float(
        jnp.max(jnp.abs(e))) + 1e-5


def test_compressed_pod_allreduce_shard_map():
    """2-'pod' mesh: compressed mean ≈ true mean of per-pod grads."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices (run under forced device count)")
    mesh = jax.make_mesh((2,), ("pod",))
    from jax.sharding import PartitionSpec as P
    g_local = jnp.stack([jnp.full((8,), 1.0), jnp.full((8,), 3.0)])

    def f(g, e):
        from repro.optim.compress import compressed_pod_allreduce
        avg, new_e = compressed_pod_allreduce({"w": g[0]}, {"w": e[0]},
                                              "pod")
        return avg["w"][None], new_e["w"][None]

    sharded = jax.shard_map(f, mesh=mesh,
                            in_specs=(P("pod"), P("pod")),
                            out_specs=(P("pod"), P("pod")),
                            check_vma=False)
    avg, _ = sharded(g_local, jnp.zeros((2, 8)))
    np.testing.assert_allclose(np.asarray(avg), 2.0, rtol=1e-2)
