"""Jit'd public wrappers for the Pallas kernels.

``flash_attention`` carries a custom VJP whose backward pass recomputes
attention through the pure-jnp reference (FlashAttention backward kernels
are out of scope — the paper has no kernel contribution; these kernels
serve the serving/prefill hot path, and training through them remains
correct via this fallback).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode as _flash_decode_impl


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, scale, causal=True, window=0, softcap=0.0,
                    interpret=False):
    """q: (B, S, NH, hd); k, v: (B, S, KV, hd) -> (B, S, NH, hd)."""
    return flash_attention_fwd(q, k, v, scale=scale, causal=causal,
                               window=window, softcap=softcap,
                               interpret=interpret)


def _fa_fwd(q, k, v, scale, causal, window, softcap, interpret):
    out = flash_attention_fwd(q, k, v, scale=scale, causal=causal,
                              window=window, softcap=softcap,
                              interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(scale, causal, window, softcap, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.attention(q, k, v, scale=scale, causal=causal,
                                      window=window, softcap=softcap),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_decode(q, k_cache, v_cache, pos, *, scale, window=0, softcap=0.0,
                 interpret=False):
    """q: (B, NH, hd); caches: (B, S, KV, hd); pos scalar -> (B, NH, hd)."""
    return _flash_decode_impl(q, k_cache, v_cache, pos, scale=scale,
                              window=window, softcap=softcap,
                              interpret=interpret)
