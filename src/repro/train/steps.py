"""Train / prefill / decode step builders with mesh-resolved shardings.

``make_step_bundle(cfg, mesh, shape)`` returns everything the launcher and
the dry-run need for one (arch × input-shape) cell:

- ``train_step(state, batch)``  (shape.kind == "train")
- ``prefill(params, inputs)``   (shape.kind == "prefill")
- ``serve_step(params, cache, tokens, pos)``  (shape.kind == "decode")
- input ShapeDtypeStructs and in/out shardings for ``jax.jit(...).lower``.

Distribution design (DESIGN.md §4): batch shards over ("pod","data");
tensor dims over "model" via the logical-axis resolver; ``fsdp_params``
additionally shards the d_model dim of weights over the data axes
(ZeRO-3).  Microbatching splits the global batch into ``cfg.microbatches``
scan steps so XLA can overlap reduce-scatter of microbatch *k*'s grads
with microbatch *k+1*'s compute.  Optional int8+error-feedback gradient
compression runs across the "pod" (DCN) axis only, via a partial-manual
``shard_map``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCfg
from repro.distributed.sharding import (
    activation_sharding, batch_spec, logical_rules, resolve_axes_tree,
)
from repro.models import Model
from repro.optim import AdamW, OptConfig, cosine_warmup
from repro.optim.compress import compressed_pod_allreduce, ef_init


def _dt(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def _inputs_struct(cfg: ArchConfig, B: int, S: int):
    if cfg.input_mode == "tokens":
        return jax.ShapeDtypeStruct((B, S), jnp.int32)
    # vlm/audio stubs: precomputed patch/frame embeddings
    return jax.ShapeDtypeStruct((B, S, cfg.d_model), _dt(cfg.compute_dtype))


def train_input_specs(cfg: ArchConfig, shape: ShapeCfg) -> dict:
    B, S = shape.global_batch, shape.seq_len
    return {
        "inputs": _inputs_struct(cfg, B, S),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }


def serve_input_specs(cfg: ArchConfig, shape: ShapeCfg) -> dict:
    """Decode: one new token against a seq_len KV cache."""
    B = shape.global_batch
    model = Model(cfg)
    cache = jax.eval_shape(
        lambda: model.init_cache(B, shape.seq_len, _dt(cfg.cache_dtype)))
    return {
        "cache": cache,
        "tokens": _inputs_struct(cfg, B, 1),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Sharding resolution
# ---------------------------------------------------------------------------


def _spec_tree(axes_tree, shapes_tree, cfg, mesh, extra_rules=None):
    rules = logical_rules(cfg, mesh)
    if extra_rules:
        rules.update(extra_rules)
    return jax.tree.map(
        lambda axes, val: _resolve_one(axes, val.shape, rules, mesh),
        axes_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def _resolve_one(axes, shape, rules, mesh):
    from repro.distributed.sharding import resolve_spec
    return resolve_spec(axes, shape, rules, mesh)


def decode_cache_rules(cfg: ArchConfig, mesh: Mesh) -> dict:
    """Adaptive decode-cache sharding.

    If KV heads don't divide the model axis (MQA/GQA with few KV heads),
    shard the cache *sequence* dim over "model" instead (context-parallel
    decode) so the cache doesn't replicate 16x.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes.get("model", 1)
    rules = {}
    if cfg.n_kv_heads % m != 0:
        rules["cache_seq"] = ("model",)
    return rules


# ---------------------------------------------------------------------------
# Bundle
# ---------------------------------------------------------------------------


@dataclass
class StepBundle:
    cfg: ArchConfig
    shape: ShapeCfg
    mesh: Optional[Mesh]
    model: Model
    step_fn: Callable            # the function to jit / lower
    in_specs: tuple              # ShapeDtypeStruct args for .lower()
    in_shardings: Any
    out_shardings: Any
    init_fn: Optional[Callable] = None   # real-run state init (train only)


def _state_axes(model: Model, compression: bool) -> dict:
    paxes = model.param_axes()
    axes = {
        "params": paxes,
        "opt": {"m": paxes, "v": paxes, "step": ()},
    }
    if compression:
        axes["ef"] = paxes
    return axes


def _state_shapes(model: Model, cfg: ArchConfig, opt: AdamW,
                  compression: bool) -> dict:
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    state = {"params": params, "opt": opt_state}
    if compression:
        state["ef"] = jax.eval_shape(ef_init, params)
    return state


def make_opt(cfg: ArchConfig, total_steps: int = 100_000) -> AdamW:
    oc = OptConfig(state_dtype=cfg.opt_dtype)
    return AdamW(oc, cosine_warmup(oc.lr, 2_000, total_steps))


def make_step_bundle(cfg: ArchConfig, shape: ShapeCfg,
                     mesh: Optional[Mesh] = None, *,
                     donate: bool = True) -> StepBundle:
    model = Model(cfg)
    if shape.kind == "train":
        return _train_bundle(cfg, shape, mesh, model, donate)
    if shape.kind == "prefill":
        return _prefill_bundle(cfg, shape, mesh, model)
    if shape.kind == "decode":
        return _decode_bundle(cfg, shape, mesh, model)
    raise ValueError(shape.kind)


# --- train -----------------------------------------------------------------


def _train_bundle(cfg, shape, mesh, model, donate) -> StepBundle:
    opt = make_opt(cfg)
    compression = cfg.grad_compression == "int8" and mesh is not None \
        and "pod" in mesh.axis_names

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, loss, metrics

    def accumulate(params, batch):
        """Microbatched gradient accumulation via lax.scan."""
        k = cfg.microbatches
        if k <= 1:
            return grads_of(params, batch)
        B = batch["labels"].shape[0]
        assert B % k == 0, (B, k)

        def resh(x):
            xm = x.reshape((k, B // k) + x.shape[1:])
            if mesh is not None:
                xm = jax.lax.with_sharding_constraint(
                    xm, NamedSharding(mesh, P(None, *batch_spec(mesh, 0))))
            return xm

        mb = jax.tree.map(resh, batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          jax.eval_shape(lambda: model.init_params(
                              jax.random.key(0))))

        def body(carry, mb_i):
            gsum, lsum = carry
            g, l, _ = grads_of(params, mb_i)
            gsum = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (gsum, lsum + l), None

        (gsum, lsum), _ = jax.lax.scan(
            body, (g0, jnp.zeros((), jnp.float32)), mb)
        grads = jax.tree.map(lambda g: g / k, gsum)
        return grads, lsum / k, {}

    def apply_update(state, grads, loss, extra):
        params, new_opt = opt.update(grads, state["opt"], state["params"])
        new_state = {"params": params, "opt": new_opt, **extra}
        metrics = {"loss": loss, "step": new_opt["step"]}
        return new_state, metrics

    if compression:
        def train_step(state, batch):
            with activation_sharding(mesh, cfg):
                def per_pod(params, ef, batch):
                    # "pod" is manual inside this region: constraints must
                    # only mention the auto axes
                    with activation_sharding(mesh, cfg, exclude=("pod",)):
                        grads, loss, _ = accumulate(params, batch)
                    grads, new_ef = compressed_pod_allreduce(grads, ef,
                                                             "pod")
                    loss = jax.lax.pmean(loss, "pod")
                    return grads, new_ef, loss

                sharded = jax.shard_map(
                    per_pod, mesh=mesh, axis_names={"pod"},
                    in_specs=(P(), P(), P("pod")), out_specs=(P(), P(), P()),
                    check_vma=False)
                grads, new_ef, loss = sharded(state["params"], state["ef"],
                                              batch)
                return apply_update(state, grads, loss, {"ef": new_ef})
    else:
        def train_step(state, batch):
            with activation_sharding(mesh, cfg):
                grads, loss, _ = accumulate(state["params"], batch)
                return apply_update(state, grads, loss, {})

    state_shapes = _state_shapes(model, cfg, opt, compression)
    batch_shapes = train_input_specs(cfg, shape)

    if mesh is None:
        in_sh = out_sh = None
        batch_sharding = None
    else:
        axes = _state_axes(model, compression)
        state_specs = {
            "params": _spec_tree(axes["params"], state_shapes["params"],
                                 cfg, mesh),
            "opt": {
                "m": _spec_tree(axes["params"], state_shapes["params"],
                                cfg, mesh),
                "v": _spec_tree(axes["params"], state_shapes["params"],
                                cfg, mesh),
                "step": P(),
            },
        }
        if compression:
            # error-feedback buffers live per-pod: replicate like params
            state_specs["ef"] = state_specs["params"]
        bspec = batch_spec(mesh, extra_dims=1,
                           batch_size=shape.global_batch)
        bspec3 = batch_spec(mesh, extra_dims=2,
                            batch_size=shape.global_batch)
        batch_sharding = {
            "inputs": NamedSharding(
                mesh, bspec if cfg.input_mode == "tokens" else bspec3),
            "labels": NamedSharding(mesh, bspec),
        }
        to_named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        in_sh = (to_named(state_specs), batch_sharding)
        metrics_sh = {"loss": NamedSharding(mesh, P()),
                      "step": NamedSharding(mesh, P())}
        out_sh = (to_named(state_specs), metrics_sh)

    def init_fn(key):
        params = model.init_params(key)
        state = {"params": params, "opt": make_opt(cfg).init(params)}
        if compression:
            state["ef"] = ef_init(params)
        return state

    return StepBundle(cfg, shape, mesh, model, train_step,
                      (state_shapes, batch_shapes), in_sh, out_sh, init_fn)


# --- prefill ------------------------------------------------------------


def _prefill_bundle(cfg, shape, mesh, model) -> StepBundle:
    def prefill(params, inputs):
        with activation_sharding(mesh, cfg):
            logits, cache = model.prefill(params, inputs)
            return logits, cache

    params_shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    inputs_struct = _inputs_struct(cfg, shape.global_batch, shape.seq_len)

    if mesh is None:
        in_sh = out_sh = None
    else:
        pspecs = _spec_tree(model.param_axes(), params_shapes, cfg, mesh)
        to_named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        extra = 1 if cfg.input_mode == "tokens" else 2
        in_sh = (to_named(pspecs),
                 NamedSharding(mesh, batch_spec(
                     mesh, extra_dims=extra,
                     batch_size=shape.global_batch)))
        out_sh = None   # let the partitioner place logits + cache
    return StepBundle(cfg, shape, mesh, model, prefill,
                      (params_shapes, inputs_struct), in_sh, out_sh)


# --- decode -----------------------------------------------------------------


def _decode_bundle(cfg, shape, mesh, model) -> StepBundle:
    def serve_step(params, cache, tokens, pos):
        with activation_sharding(mesh, cfg):
            logits, new_cache = model.decode_step(params, cache, tokens,
                                                  pos)
            return logits, new_cache

    params_shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    io = serve_input_specs(cfg, shape)

    if mesh is None:
        in_sh = out_sh = None
    else:
        pspecs = _spec_tree(model.param_axes(), params_shapes, cfg, mesh)
        extra_rules = decode_cache_rules(cfg, mesh)
        cspecs = _spec_tree(model.cache_axes(), io["cache"], cfg, mesh,
                            extra_rules=extra_rules)
        to_named = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        extra = 1 if cfg.input_mode == "tokens" else 2
        in_sh = (to_named(pspecs), to_named(cspecs),
                 NamedSharding(mesh, batch_spec(
                     mesh, extra_dims=extra,
                     batch_size=shape.global_batch)),
                 NamedSharding(mesh, P()))
        out_sh = (None, to_named(cspecs))   # cache stays put (donated)
    return StepBundle(cfg, shape, mesh, model, serve_step,
                      (params_shapes, io["cache"], io["tokens"], io["pos"]),
                      in_sh, out_sh)
