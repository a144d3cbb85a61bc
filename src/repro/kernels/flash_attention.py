"""Pallas TPU flash-attention forward kernel (GQA, causal, window, softcap).

Tiling: grid = (B·NH, Sq/bq, Sk/bk); the (bq, hd) output block is revisited
across the innermost k dimension with VMEM scratch carrying the online-
softmax state (acc, m, l) — the standard TPU mapping of FlashAttention,
where block shapes bound the VMEM working set (bq·hd + 2·bk·hd + bq·hd
floats) and the (bq, bk) logit tile feeds the MXU.

GQA is handled in the index maps: query-head program ``bh`` reads KV head
``bh // group``, so each KV block is fetched once per head group.

Numerics: f32 accumulation regardless of input dtype; gemma2-style tanh
soft-capping applied to the logit tile before masking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, softcap: float, causal: bool, window: int,
                bq: int, bk: int, k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                  # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)                            # (bq, bk)
    alpha = jnp.exp(m_prev - m_cur)                   # (bq, 1)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def pick_block(s: int, want: int) -> int:
    """Block length along a sequence axis of length ``s``.

    Mosaic tiles the second-to-last block dim in rows of 8, so a block
    is either the whole axis or a multiple of 8 that divides it; any
    other length raises here instead of in the TPU compiler.
    """
    if s <= want:
        return s
    b = want
    while b >= 8 and s % b:
        b //= 2
    if b < 8 or b % 8:
        raise ValueError(
            f"no block of at most {want} rows tiles a sequence of {s}: "
            "a block must be the whole axis or a multiple of 8 dividing "
            "it; pad the sequence to a multiple of 8")
    return b


def flash_attention_fwd(q, k, v, *, scale: float, causal: bool = True,
                        window: int = 0, softcap: float = 0.0,
                        block_q: int = 256, block_k: int = 256,
                        interpret: bool = False):
    """q: (B, Sq, NH, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, NH, hd).

    Compiled for the TPU unless ``interpret=True`` (tests on the CPU);
    lowering for any other platform raises.
    """
    B, Sq, NH, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    assert NH % KV == 0, (NH, KV)
    G = NH // KV

    bq = pick_block(Sq, block_q)
    bk = pick_block(Sk, block_k)
    k_blocks = Sk // bk

    qh = q.transpose(0, 2, 1, 3).reshape(B * NH, Sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, hd)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, softcap=softcap, causal=causal,
        window=window, bq=bq, bk=bk, k_blocks=k_blocks)

    out = pl.pallas_call(
        kernel,
        grid=(B * NH, Sq // bq, k_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh // G, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, qi, ki: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * NH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
            pltpu.VMEM((bq, 1), jnp.float32),     # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),     # l (running denom)
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        backend="mosaic_tpu",        # any other platform refuses to lower
    )(qh, kh, vh)
    return out.reshape(B, NH, Sq, hd).transpose(0, 2, 1, 3)
