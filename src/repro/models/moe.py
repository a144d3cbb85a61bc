"""Top-k routed mixture-of-experts with capacity-bounded scatter dispatch.

Dispatch is scatter/gather-based (sorted-slot style), NOT the GShard one-hot
einsum: tokens are scattered into an (experts, capacity, d_model) buffer via
``.at[].add`` and gathered back after the expert matmuls.  The one-hot einsum
dispatch costs O(T*E*C*D) FLOPs — for the 128-expert llama4 config that is
*more* FLOPs than the experts themselves — whereas scatter dispatch is
O(T*D) bytes moved.  Expert weights carry an "expert" logical axis, so on a
16-way tensor axis llama4's 128 experts shard 8-per-device (EP) while
granite-moe's 40 experts fall back to sharding the tiny expert FFN dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import act_constrain
from repro.models.params import pmeta, dense_init
from repro.models.layers import _act


def _dt(name: str):
    return jnp.dtype(name)


def init_moe(key, cfg) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff, m.padded_experts()
    dt = _dt(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    p = {
        "router": pmeta(dense_init(ks[0], (d, e), dt), ("embed", "expert")),
        "w_up": pmeta(dense_init(ks[1], (e, d, f), dt),
                      ("expert", "embed", "expert_ffn")),
        "w_down": pmeta(dense_init(ks[2], (e, f, d), dt),
                        ("expert", "expert_ffn", "embed")),
    }
    if cfg.gated_mlp:
        p["w_gate"] = pmeta(dense_init(ks[3], (e, d, f), dt),
                            ("expert", "embed", "expert_ffn"))
    if m.shared_expert:
        p["shared_up"] = pmeta(dense_init(ks[4], (d, f), dt), ("embed", "ffn"))
        p["shared_down"] = pmeta(dense_init(ks[5], (f, d), dt), ("ffn", "embed"))
        if cfg.gated_mlp:
            p["shared_gate"] = pmeta(dense_init(ks[6], (d, f), dt),
                                     ("embed", "ffn"))
    return p


def moe_apply(params, x, cfg):
    """x: (B, S, D) -> (B, S, D).  Returns (out, aux) with load-balance loss."""
    if cfg.moe.ep_shard:
        from repro.distributed.sharding import current_mesh
        mesh = current_mesh()
        if mesh is not None and _ep_applicable(cfg, x, mesh):
            return _moe_apply_ep(params, x, cfg, mesh)
    return _moe_apply_dense(params, x, cfg)


def _moe_apply_dense(params, x, cfg):
    m = cfg.moe
    cdt = _dt(cfg.compute_dtype)
    B, S, D = x.shape
    T = B * S
    k = m.top_k
    E = m.padded_experts()
    xf = x.reshape(T, D).astype(cdt)

    # --- routing (f32 for numerical stability) ---------------------------
    router_logits = xf.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if E > m.num_experts:
        # §Perf expert padding: dead experts never win the top-k
        pad = jnp.full((T, E - m.num_experts), -1e30, jnp.float32)
        router_logits = jnp.concatenate(
            [router_logits[:, :m.num_experts], pad], axis=-1)
    probs = jax.nn.softmax(router_logits, axis=-1)          # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)         # (T, k)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)        # renormalize

    # --- capacity-bounded slot assignment ---------------------------------
    # Slot positions come from a *grouped* two-level cumsum: local prefix
    # sums within G token groups (no cross-shard dependency — groups align
    # with the data shards) plus a tiny (G, E) exclusive scan across
    # groups.  Equivalent ordering to a flat token-major cumsum, but the
    # partitioner keeps the big (T*k, E) scan local instead of
    # all-gathering it across the data axis.
    capacity = max(1, int(m.capacity_factor * T * k / m.num_experts))
    flat_ids = expert_ids.reshape(T * k)                    # token-major
    TK = T * k
    G = 1
    while G < 1024 and TK % (2 * G) == 0 and TK // (2 * G) >= 1:
        G *= 2
    ids_g = flat_ids.reshape(G, TK // G)
    onehot = jax.nn.one_hot(ids_g, E, dtype=jnp.int32)      # (G, TL, E)
    onehot = act_constrain(onehot, ("act_tokens", None, None))
    local_pos = jnp.cumsum(onehot, axis=1) - onehot         # (G, TL, E)
    counts = jnp.sum(onehot, axis=1)                        # (G, E)
    offsets = jnp.cumsum(counts, axis=0) - counts           # exclusive, (G,E)
    pos_in_expert = (local_pos + offsets[:, None, :]).reshape(TK, E)
    slot = jnp.take_along_axis(
        pos_in_expert, flat_ids[:, None], axis=1)[:, 0]     # (T*k,)
    keep = slot < capacity
    token_idx = jnp.repeat(jnp.arange(T), k)

    # --- scatter tokens into (E, C, D) ------------------------------------
    safe_slot = jnp.where(keep, slot, 0)
    contrib = jnp.where(keep[:, None], xf[token_idx], 0)
    xe = jnp.zeros((E, capacity, D), cdt).at[flat_ids, safe_slot].add(
        jnp.where(keep[:, None], contrib, 0))
    # expert-parallel layout: (E, C, D) sharded over the expert axis, the
    # capacity dim over the DP axes (the buffer scales with global tokens)
    xe = act_constrain(xe, ("expert", "act_tokens", None))

    # --- expert FFN --------------------------------------------------------
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(cdt))
    if cfg.gated_mlp:
        gate = _act(cfg.act)(
            jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(cdt)))
        h = gate * up
    else:
        h = _act(cfg.act)(up)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(cdt))
    ye = act_constrain(ye, ("expert", "act_tokens", None))

    # --- gather back + combine --------------------------------------------
    gathered = ye[flat_ids, safe_slot]                      # (T*k, D)
    weights = jnp.where(keep, gate_vals.reshape(T * k), 0).astype(cdt)
    out = jnp.zeros((T, D), cdt).at[token_idx].add(gathered * weights[:, None])

    if m.shared_expert:
        s_up = xf @ params["shared_up"].astype(cdt)
        if cfg.gated_mlp:
            s_gate = _act(cfg.act)(xf @ params["shared_gate"].astype(cdt))
            s_h = s_gate * s_up
        else:
            s_h = _act(cfg.act)(s_up)
        out = out + s_h @ params["shared_down"].astype(cdt)

    # --- auxiliary load-balance loss (Switch-style) ------------------------
    me = jnp.mean(probs, axis=0)                            # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux_loss = m.num_experts * jnp.sum(me * ce)
    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))

    return out.reshape(B, S, D), {"moe_aux": aux_loss, "moe_drop": drop_frac}


# ---------------------------------------------------------------------------
# Explicit expert parallelism via shard_map (§Perf hillclimb)
# ---------------------------------------------------------------------------
#
# Under pjit alone the partitioner reduces global (T·k, D) dispatch/combine
# buffers with all-reduces over the data axis (measured: the dominant ICI
# term on granite-moe).  The explicit formulation exploits the actual
# layout: tokens are *replicated* over the model axis and sharded over the
# DP axes, experts are sharded over the model axis — so every model shard
# routes its local tokens over all experts, computes only its own experts
# with *local* capacity, and a single psum over "model" combines the top-k
# contributions.  Communication per MoE layer: one (T_local, D) psum
# (+ a tiny (T_local, E) logit all-gather), instead of global all-reduces.


def _ep_applicable(cfg, x, mesh) -> bool:
    import math
    m = cfg.moe
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "model" not in sizes:
        return False
    dp = [a for a in ("pod", "data") if a in sizes]
    dp_size = math.prod(sizes[a] for a in dp)
    E = m.padded_experts()
    T = x.shape[0] * x.shape[1]
    return (E % sizes["model"] == 0
            and x.shape[0] % dp_size == 0
            and (T // dp_size) * m.top_k >= 4 * E)   # enough local tokens


def _moe_apply_ep(params, x, cfg, mesh):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    cdt = _dt(cfg.compute_dtype)
    E = m.padded_experts()
    k = m.top_k
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else dp[0]

    def local_fn(x_l, router, w_up, w_gate, w_down):
        Bl, S, D = x_l.shape
        T = Bl * S
        E_l = w_up.shape[0]                      # experts on this shard
        xf = x_l.reshape(T, D).astype(cdt)

        # --- routing: local logits for owned experts, gathered to full E
        logits_l = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        logits = jax.lax.all_gather(logits_l, "model", axis=1, tiled=True)
        if E > m.num_experts:
            col = jnp.arange(E)[None, :]
            logits = jnp.where(col < m.num_experts, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

        # --- local-capacity slot assignment (GShard per-group capacity)
        capacity = max(1, int(m.capacity_factor * T * k / m.num_experts))
        flat_ids = expert_ids.reshape(T * k)
        onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.take_along_axis(pos, flat_ids[:, None], 1)[:, 0]
        my = jax.lax.axis_index("model")
        lo = my * E_l
        owned = (flat_ids >= lo) & (flat_ids < lo + E_l)
        keep = (slot < capacity) & owned
        local_ids = jnp.where(keep, flat_ids - lo, 0)
        safe_slot = jnp.where(keep, slot, 0)
        token_idx = jnp.repeat(jnp.arange(T), k)

        contrib = jnp.where(keep[:, None], xf[token_idx], 0)
        xe = jnp.zeros((E_l, capacity, D), cdt).at[
            local_ids, safe_slot].add(contrib)

        up = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(cdt))
        if w_gate is not None:
            g = _act(cfg.act)(jnp.einsum("ecd,edf->ecf", xe,
                                         w_gate.astype(cdt)))
            hidden = g * up
        else:
            hidden = _act(cfg.act)(up)
        ye = jnp.einsum("ecf,efd->ecd", hidden, w_down.astype(cdt))

        gathered = ye[local_ids, safe_slot]                  # (T*k, D)
        weights = jnp.where(keep, gate_vals.reshape(T * k), 0).astype(cdt)
        partial = jnp.zeros((T, D), cdt).at[token_idx].add(
            gathered * weights[:, None])
        out = jax.lax.psum(partial, "model")                 # EP combine

        # aux metrics (identical across model shards; mean over DP)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E,
                                     dtype=jnp.float32), axis=0)
        aux = m.num_experts * jnp.sum(me * ce)
        drop = 1.0 - jnp.mean(((slot < capacity)).astype(jnp.float32))
        if dp:
            aux = jax.lax.pmean(aux, dp)
            drop = jax.lax.pmean(drop, dp)
        return out.reshape(Bl, S, D), aux, drop

    w_gate = params.get("w_gate")
    in_specs = (P(dp_spec, None, None), P(None, "model"),
                P("model", None, None),
                (P("model", None, None) if w_gate is not None else P()),
                P("model", None, None))
    out_specs = (P(dp_spec, None, None), P(), P())
    sharded = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    out, aux, drop = sharded(
        x, params["router"],
        params["w_up"],
        w_gate if w_gate is not None else jnp.zeros((), cdt),
        params["w_down"])

    if m.shared_expert:
        B, S, D = x.shape
        xf = x.reshape(B * S, D).astype(cdt)
        s_up = xf @ params["shared_up"].astype(cdt)
        if cfg.gated_mlp:
            s_h = _act(cfg.act)(xf @ params["shared_gate"].astype(cdt)) \
                * s_up
        else:
            s_h = _act(cfg.act)(s_up)
        out = out + (s_h @ params["shared_down"].astype(cdt)).reshape(
            B, S, D)

    return out, {"moe_aux": aux, "moe_drop": drop}
