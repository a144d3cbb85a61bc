"""Pallas kernel sweeps vs the pure-jnp oracle.

The kernels compile only for the TPU; on the CPU these tests ask for
interpret mode explicitly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def t(shape, dtype):
    return jnp.asarray(RNG.normal(0, 1, shape), dtype)


FWD_CASES = [
    # B, S, NH, KV, hd, window, softcap
    (2, 64, 4, 4, 32, 0, 0.0),       # MHA
    (2, 128, 8, 2, 64, 0, 0.0),      # GQA 4:1
    (1, 256, 8, 1, 64, 0, 0.0),      # MQA
    (1, 128, 4, 2, 32, 32, 0.0),     # sliding window
    (1, 128, 4, 2, 32, 0, 50.0),     # softcap (gemma2)
    (1, 96, 2, 2, 16, 24, 30.0),     # window + softcap, odd sizes
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FWD_CASES)
def test_flash_attention_vs_ref(case, dtype):
    B, S, NH, KV, hd, window, cap = case
    q, k, v = t((B, S, NH, hd), dtype), t((B, S, KV, hd), dtype), \
        t((B, S, KV, hd), dtype)
    scale = hd ** -0.5
    out = ops.flash_attention(q, k, v, scale, True, window, cap, True)
    want = ref.attention(q, k, v, scale=scale, causal=True, window=window,
                         softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


DECODE_CASES = [
    (2, 128, 4, 4, 32, 64, 0, 0.0),
    (2, 256, 8, 2, 64, 255, 0, 0.0),
    (1, 512, 8, 1, 64, 0, 0, 0.0),      # pos=0: single valid key
    (1, 256, 4, 2, 32, 200, 64, 0.0),   # window
    (1, 128, 4, 4, 32, 100, 0, 50.0),   # softcap
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_flash_decode_vs_ref(case, dtype):
    B, S, NH, KV, hd, pos, window, cap = case
    q = t((B, NH, hd), dtype)
    kc, vc = t((B, S, KV, hd), dtype), t((B, S, KV, hd), dtype)
    scale = hd ** -0.5
    out = ops.flash_decode(q, kc, vc, pos, scale=scale, window=window,
                           softcap=cap, interpret=True)
    want = ref.decode(q, kc, vc, pos, scale=scale, window=window,
                      softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


def test_flash_attention_grads_match_ref():
    q, k, v = t((1, 64, 4, 32), jnp.float32), t((1, 64, 2, 32),
                                                jnp.float32), \
        t((1, 64, 2, 32), jnp.float32)
    s = 32 ** -0.5

    def f(q, k, v):
        return jnp.sum(
            ops.flash_attention(q, k, v, s, True, 0, 0.0, True) ** 2)

    def fr(q, k, v):
        return jnp.sum(ref.attention(q, k, v, scale=s) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_flash_blocks_do_not_change_result():
    """Block-shape sweep: tiling must be semantics-preserving."""
    from repro.kernels.flash_attention import flash_attention_fwd
    q, k, v = t((1, 128, 4, 32), jnp.float32), \
        t((1, 128, 2, 32), jnp.float32), t((1, 128, 2, 32), jnp.float32)
    outs = [
        flash_attention_fwd(q, k, v, scale=0.1, block_q=bq, block_k=bk,
                            interpret=True)
        for bq, bk in [(32, 32), (64, 128), (128, 64), (16, 16)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


def test_kernels_refuse_non_tpu_backend_without_interpret():
    """No silent interpret fallback: off the TPU, lowering raises."""
    q, k = t((1, 64, 4, 32), jnp.float32), t((1, 64, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        ops.flash_attention(q, k, k, 0.1, True, 0, 0.0)
    with pytest.raises(ValueError, match="interpret"):
        ops.flash_decode(q[:, 0], k, k, 3, scale=0.1)


def test_chunked_xla_attention_matches_ref():
    """The XLA fallback (q-chunked flash-style) equals the oracle too."""
    from repro.models import attention as attn
    from repro.configs import get_config, reduce_for_smoke
    cfg = reduce_for_smoke(get_config("qwen2-7b"))
    B, S, NH, KV, hd = 2, 96, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q, k, v = t((B, S, NH, hd), jnp.float32), t((B, S, KV, hd),
                                                jnp.float32), \
        t((B, S, KV, hd), jnp.float32)
    out = attn.full_attention(q, k, v, cfg, window=0, q_chunk=32)
    want = ref.attention(q, k, v, scale=hd ** -0.5, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
