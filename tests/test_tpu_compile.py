"""Compiles of the main path's device programs for a described TPU v5e.

The TPU compiler runs here without a chip attached: it lowers and
compiles for a ``v5e:2x2`` topology description and refuses what the chip
would refuse (misaligned Pallas blocks, too much VMEM, too much HBM),
which interpret mode on the CPU never catches.  Nothing runs, so these
tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
tests run under several pytest-xdist workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.spe import FraudSVMQuery, TrafficMetricsQuery
from repro.core.spec import SPE, Component
from repro.kernels.flash_attention import flash_attention_fwd, pick_block
from repro.kernels.flash_decode import flash_decode


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile;
    # and keep the TPU compiler's logs out of the shared /tmp
    was = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("bucket", [2048, 4096])
def test_traffic_metrics_program_compiles(one_chip, bucket):
    f = TrafficMetricsQuery(Component(SPE))._metrics_fn(bucket)
    compiled = f.lower(_spec((bucket,), jnp.int32, one_chip),
                       _spec((bucket,), jnp.float32, one_chip),
                       _spec((bucket,), jnp.bool_, one_chip)).compile()
    # service ids (int32), sizes (float32) and the valid mask (bool)
    assert compiled.memory_analysis().argument_size_in_bytes == 9 * bucket


def test_fraud_svm_scorer_compiles(one_chip):
    q = FraudSVMQuery(Component(SPE))
    compiled = q._score.lower(
        _spec((2048, q.dim), jnp.float32, one_chip)).compile()
    assert compiled.memory_analysis().argument_size_in_bytes == \
        2048 * q.dim * 4


# (B, S, NH, KV, hd, window, softcap) at published widths
ATTN_WIDTHS = {
    name: (1, 4096, c.n_heads, c.n_kv_heads, c.head_dim_,
           c.sliding_window if c.attn_softcap else 0, c.attn_softcap)
    for name, c in ((n, get_config(n)) for n in ("qwen2-7b", "gemma2-2b"))
}


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_attention_compiles_to_mosaic(one_chip, arch):
    B, S, NH, KV, hd, window, cap = ATTN_WIDTHS[arch]
    q = _spec((B, S, NH, hd), jnp.bfloat16, one_chip)
    kv = _spec((B, S, KV, hd), jnp.bfloat16, one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention_fwd(q, k, v, scale=hd ** -0.5,
                                            window=window, softcap=cap),
        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_decode_compiles_to_mosaic(one_chip, arch):
    B, S, NH, KV, hd, window, cap = ATTN_WIDTHS[arch]
    q = _spec((B, NH, hd), jnp.bfloat16, one_chip)
    cache = _spec((B, S, KV, hd), jnp.bfloat16, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    compiled = _compile(
        lambda q, k, v, pos: flash_decode(q, k, v, pos, scale=hd ** -0.5,
                                          window=window, softcap=cap),
        q, cache, cache, pos)
    assert "tpu_custom_call" in compiled.as_text()


def test_unaligned_sequence_raises_before_the_compiler(one_chip):
    # halving 256 reaches 4 rows for S=300, which Mosaic refuses
    with pytest.raises(ValueError, match="multiple of 8"):
        pick_block(300, 256)
    x = _spec((1, 300, 4, 128), jnp.bfloat16, one_chip)
    with pytest.raises(ValueError, match="multiple of 8"):
        _compile(lambda q: flash_attention_fwd(q, q, q, scale=0.1), x)
    assert pick_block(4096, 256) == 256 and pick_block(96, 256) == 96
    assert all(pick_block(s, 256) % 8 == 0 for s in (264, 520, 4104))
