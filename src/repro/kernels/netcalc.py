"""Delay/bandwidth arithmetic for the network model (numpy-first).

The inner loop of :meth:`repro.core.netem.Network.transfer` — propagation
latency plus serialization at the bottleneck link — lives here so cohort
fusion (``transfer_many``) runs it as one vectorized computation and so a
Pallas kernel can slot in behind the same signatures for offline
throughput experiments.

Backend contract:

- ``numpy`` (default, and the only fingerprint-safe backend): float64
  element-wise IEEE ops, bitwise identical to the scalar composition in
  the on-demand hop walk (``lat + nbytes / bw``; ``x / inf == 0.0``
  reproduces the ``bw < inf`` serialization guard exactly).
- ``jax`` (opt-in via ``REPRO_NETCALC_BACKEND=jax``): jit-compiled, kept
  Pallas-ready — flat float64 arrays in, one float64 array out, no data-
  dependent shapes.  JAX is imported lazily inside the backend switch,
  never at module scope (the warm-pool contract: importing this module
  must not pull in jax).  x64 is required; without it the backend raises
  rather than silently returning float32 (which would break the
  bit-identity contract this module exists to preserve).

Everything in the emulator's deterministic hot path uses the numpy
backend unconditionally.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np


def delay_s(lat_s: float, bneck_Bps: float, nbytes: int) -> float:
    """Scalar transfer delay: latency + serialization at the bottleneck.

    Bitwise identical to the on-demand composition
    ``lat + (nbytes / bw if bw < inf else 0.0)``: division by ``inf``
    yields exactly ``0.0`` and ``lat + 0.0 == lat`` for the nonnegative
    latencies the model produces.
    """
    return lat_s + nbytes / bneck_Bps


def _delay_many_np(lat_s: np.ndarray, bneck_Bps: np.ndarray, nbytes: int,
                   extra_s: Optional[np.ndarray]) -> np.ndarray:
    out = lat_s + nbytes / bneck_Bps
    if extra_s is not None:
        out = out + extra_s
    return out


def _delay_many_jax(lat_s, bneck_Bps, nbytes, extra_s):
    from repro import chip
    chip.init_jax()               # refuses in sweep pool workers
    import jax
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "netcalc jax backend needs float64 (jax_enable_x64); "
            "float32 would break the delay bit-identity contract")
    import jax.numpy as jnp
    out = jnp.asarray(lat_s) + float(nbytes) / jnp.asarray(bneck_Bps)
    if extra_s is not None:
        out = out + jnp.asarray(extra_s)
    return np.asarray(out)


def delay_many(lat_s: np.ndarray, bneck_Bps: np.ndarray, nbytes: int,
               extra_s: Optional[np.ndarray] = None) -> np.ndarray:
    """Vectorized cohort delay: one fused computation for a homogeneous
    (same payload size) fan-out.  ``extra_s`` carries per-destination
    slow-host extras, pre-summed with the source's (matching the scalar
    ``delay += (src_extra + dst_extra)`` association)."""
    if os.environ.get("REPRO_NETCALC_BACKEND", "numpy") == "jax":
        return _delay_many_jax(lat_s, bneck_Bps, nbytes, extra_s)
    return _delay_many_np(lat_s, bneck_Bps, nbytes, extra_s)
