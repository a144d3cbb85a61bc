"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

Decode attention is memory-bound (the whole KV cache streams through
VMEM once per token), so the kernel's job is tiling that stream: grid =
(B·KV, S/bk); each program loads a (bk, hd) K/V tile, computes the (G, bk)
logit tile for the head group against the single query, and carries the
online-softmax state in VMEM scratch.  ``pos`` arrives via scalar-memory
(SMEM) so the compiled kernel is reused for every decode step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import pick_block

NEG_INF = -2.0 ** 30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, softcap: float, window: int,
                   bk: int, k_blocks: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                  # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos <= pos
    if window:
        mask &= (pos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, pos, *, scale: float,
                 window: int = 0, softcap: float = 0.0,
                 block_k: int = 512, interpret: bool = False):
    """q: (B, NH, hd); caches: (B, S, KV, hd); pos: scalar -> (B, NH, hd).

    Compiled for the TPU unless ``interpret=True`` (tests on the CPU);
    lowering for any other platform raises.
    """
    B, NH, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    assert NH % KV == 0
    G = NH // KV

    bk = pick_block(S, block_k)
    k_blocks = S // bk

    qh = q.reshape(B * KV, G, hd)
    kh = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vh = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=softcap, window=window,
        bk=bk, k_blocks=k_blocks)

    out = pl.pallas_call(
        kernel,
        grid=(B * KV, k_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, hd), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        backend="mosaic_tpu",        # any other platform refuses to lower
    )(pos_arr, qh, kh, vh)
    return out.reshape(B, NH, hd)
