"""Logical-axis sharding rules, resolved per-mesh with divisibility checks.

The model code tags every parameter/activation dim with a *logical* name
("embed", "ffn", "heads", ...).  This module maps logical names onto mesh
axes.  Resolution is defensive: a mesh axis is only assigned when (a) the dim
size is divisible by the product of the mesh-axis sizes, and (b) the mesh
axis is not already used by another dim of the same tensor.  That single
mechanism transparently handles the awkward assigned configs — MQA (kv=1),
GQA kv=4 on a 16-way tensor axis, 40 experts on 16 shards — by falling back
to replication (or to the next dim) instead of failing to lower.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Default logical-axis -> mesh-axes candidates.  Order within the tuple is
# the sharding order; resolution drops axes that don't divide or collide.
def logical_rules(cfg, mesh: Mesh) -> dict[str, tuple[str, ...]]:
    fsdp: tuple[str, ...] = ()
    if getattr(cfg, "fsdp_params", False):
        fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if getattr(cfg, "grad_compression", "none") == "int8":
            # compressed cross-pod training: FSDP stays within the pod
            # (param all-gathers on ICI), pods exchange int8 grads on DCN
            fsdp = tuple(a for a in fsdp if a != "pod")
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    seq_axes: tuple[str, ...] = ()
    if getattr(cfg, "seq_shard", False):
        seq_axes = ("model",)     # sequence parallelism (§Perf hillclimb)
    return {
        # activations
        "batch": dp,
        "act_batch": dp,
        "act_tokens": dp,         # flattened (B*S) token dim
        "seq": (),
        "act_seq": seq_axes,
        "cache_seq": (),          # overridden adaptively for decode caches
        # parameters
        "embed": fsdp,            # d_model dim of weights (ZeRO-3 when fsdp)
        "vocab": ("model",),
        "q_features": ("model",),
        "kv_features": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ffn": ("model",),
        "expert": ("model",),
        "expert_ffn": ("model",),
        "inner": ("model",),      # mamba/mlstm inner dim
        "head_dim": (),
        "layers": (),             # stacked-scan leading dim
        "conv": (),
        "state": (),
        "low_rank": (),
    }


def resolve_spec(
    axes: tuple[Optional[str], ...],
    shape: tuple[int, ...],
    rules: dict[str, tuple[str, ...]],
    mesh: Mesh,
) -> P:
    """Resolve one tensor's logical axes into a PartitionSpec."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set[str] = set()
    out: list = []
    for dim, name in zip(shape, axes):
        cand = rules.get(name, ()) if name else ()
        cand = tuple(a for a in cand if a in sizes and a not in used)
        # greedily keep the longest prefix of candidate axes that divides dim
        picked: tuple[str, ...] = ()
        for i in range(len(cand), 0, -1):
            prefix = cand[:i]
            if dim % math.prod(sizes[a] for a in prefix) == 0:
                picked = prefix
                break
        if picked:
            used.update(picked)
            out.append(picked if len(picked) > 1 else picked[0])
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def resolve_axes_tree(axes_tree, shapes_tree, cfg, mesh: Mesh):
    """Resolve a whole axes tree (parallel to a value/shape tree) to specs."""
    rules = logical_rules(cfg, mesh)
    return jax.tree.map(
        lambda axes, val: resolve_spec(axes, val.shape, rules, mesh),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def batch_spec(mesh: Mesh, extra_dims: int = 1,
               batch_size: Optional[int] = None) -> P:
    """Spec for batch-major activations: batch over (pod, data).

    With ``batch_size``, axes that don't divide are dropped (suffix-first),
    so e.g. the long-context global_batch=1 decode replicates its inputs
    instead of failing to lower.
    """
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if batch_size is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        while axes and batch_size % math.prod(sizes[a] for a in axes):
            axes = axes[:-1]
    lead = axes if len(axes) > 1 else (axes[0] if axes else None)
    return P(lead, *([None] * extra_dims))


def constrain(x, spec: P):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


# ---------------------------------------------------------------------------
# Activation constraints (ambient, set by the step builders during tracing)
# ---------------------------------------------------------------------------

_ACT_CTX: list = []          # stack of (mesh, rules)


class activation_sharding:
    """Context manager: model code's ``act_constrain`` resolves against
    this mesh while a step function is being traced.  No context → no-op
    (pure-CPU smoke tests).  ``exclude`` drops mesh axes from every rule —
    used inside partial-manual shard_map regions where an axis (e.g.
    "pod" under gradient compression) is already manual."""

    def __init__(self, mesh: Optional[Mesh], cfg, exclude: tuple = ()):
        if mesh is None:
            self.entry = None
        else:
            rules = logical_rules(cfg, mesh)
            if exclude:
                rules = {k: tuple(a for a in v if a not in exclude)
                         for k, v in rules.items()}
            self.entry = (mesh, rules)

    def __enter__(self):
        if self.entry is not None:
            _ACT_CTX.append(self.entry)
        return self

    def __exit__(self, *exc):
        if self.entry is not None:
            _ACT_CTX.pop()
        return False


def current_mesh() -> Optional[Mesh]:
    """The mesh of the innermost activation-sharding context (or None)."""
    return _ACT_CTX[-1][0] if _ACT_CTX else None


def act_constrain(x, logical_axes: tuple):
    """Pin an activation's sharding by logical axis names (or None).

    Dims whose rule exists but fails divisibility become UNCONSTRAINED —
    pinning them replicated would override better partitioner choices
    (discovered the hard way on granite-moe's 40-expert buffers, see
    EXPERIMENTS.md §Perf).  ``None``-named dims are deliberately
    replicated.  If nothing resolves, no constraint is applied at all.
    """
    if not _ACT_CTX:
        return x
    mesh, rules = _ACT_CTX[-1]
    spec = resolve_spec(logical_axes, x.shape, rules, mesh)
    parts = list(spec) + [None] * (x.ndim - len(spec))
    if all(p is None for p in parts):
        return x
    out = []
    for name, p in zip(logical_axes, parts):
        if p is None and name is not None and rules.get(name):
            out.append(P.UNCONSTRAINED)     # wanted to shard, couldn't
        else:
            out.append(p)
    return jax.lax.with_sharding_constraint(x, P(*out))
