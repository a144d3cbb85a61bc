"""The one process that may drive the accelerator, and its compile cache.

A TPU chip belongs to one process at a time: a second process that opens
it fails or hangs.  So JAX is initialised only by the process that owns
the chip (``chip_smoke.py``, the ``repro.launch`` entry points, or the
process that calls :func:`repro.sweep.run_sweep`), never by a sweep pool
worker.  :func:`init_jax` is the single gate: every place an SPE's
query, operator or kernel backend imports JAX calls it first, and a
process marked with :func:`forbid_jax` refuses there, before JAX is
imported.

This module imports JAX only inside :func:`init_jax`, so the warm sweep
pool's fork server can preload the engine stack without it.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here sets another directory.  Otherwise the cache is
the fixed ``.jax_cache/`` directory at the root of the checkout — a
fixed path, because the path is part of the cache key.
"""
from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


class JaxForbidden(RuntimeError):
    """A JAX stage was built in a process that must not own the chip."""


_forbidden_in: Optional[str] = None      # why this process may not use JAX


def forbid_jax(where: str) -> None:
    """Mark this process as one that must never initialise JAX."""
    global _forbidden_in
    _forbidden_in = where


def init_jax() -> str:
    """Prepare this process to run JAX; returns the compile-cache dir.

    Raises :class:`JaxForbidden` in a process marked by
    :func:`forbid_jax` — it never falls back to running on the CPU.
    """
    if _forbidden_in is not None:
        raise JaxForbidden(
            f"a JAX stage was built in a {_forbidden_in}; a chip belongs "
            "to one process, so JAX stages run only in the process that "
            "owns it")
    path = os.environ.get(CACHE_ENV)
    if not path:
        import jax
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
