"""Mixture-of-Experts FFN with top-k routing.

Two execution paths:

* ``moe_dense``     — every expert runs on every token, outputs combined with
                      the sparsified router weights. Exact, O(E) compute —
                      used in reduced-config smoke tests and as the oracle the
                      capacity path is tested against (capacity -> inf).
* ``moe_capacity``  — GShard/Switch-style capacity dispatch via sort-based
                      position assignment + scatter into a [E, C, d] buffer,
                      batched expert einsum, gather-combine. Memory O(T·k),
                      not O(T·E·C): per-expert slot positions are computed by
                      a stable argsort over assignments (no [T*k, E] one-hot
                      cumsum).

Expert parallelism: experts are sharded on the ``model`` (TP) mesh axis by
annotating the expert-stacked weights with PartitionSpec("model", ...); the
SPMD partitioner turns the dispatch scatter + batched einsum + combine into
an all-to-all/all-reduce schedule. The dispatch math only involves [T*k]
index vectors, which partition cleanly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, init_dense
from repro.sharding.api import constrain


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def init_moe(key, cfg, dtype=jnp.float32):
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    std1 = 1.0 / jnp.sqrt(d)
    std2 = 1.0 / jnp.sqrt(f)
    p = {
        "router": init_dense(ks[0], d, E, bias=False, dtype=jnp.float32),
        "w1": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * std1).astype(dtype),
        "w2": (jax.random.normal(ks[2], (E, f, d), jnp.float32) * std2).astype(dtype),
    }
    if cfg.gated_mlp:
        p["w3"] = (jax.random.normal(ks[3], (E, d, f), jnp.float32) * std1).astype(dtype)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared_w1"] = init_dense(ks[4], d, fs, dtype=dtype)
        p["shared_w2"] = init_dense(jax.random.fold_in(ks[4], 1), fs, d, dtype=dtype)
        if cfg.gated_mlp:
            p["shared_w3"] = init_dense(jax.random.fold_in(ks[4], 2), d, fs, dtype=dtype)
    return p


def _router(p, x2d, cfg):
    """x2d: [T, d] -> (weights [T,k], ids [T,k], aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ p["router"]["w"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)                 # [T, k]
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance auxiliary loss
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                   # [E]
    ce = jnp.mean(
        (jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(axis=1)), axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_loss
    return weights, ids, aux


def _expert_ffn(p, h, cfg):
    """h: [E, C, d] -> [E, C, d] batched across experts."""
    a = jnp.einsum("ecd,edf->ecf", h, p["w1"].astype(h.dtype))
    a = constrain(a, "experts", None, None)
    a = act_fn(cfg.act)(a)
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", h, p["w3"].astype(h.dtype))
        g = constrain(g, "experts", None, None)
        a = a * g
    out = jnp.einsum("ecf,efd->ecd", a, p["w2"].astype(h.dtype))
    return constrain(out, "experts", None, None)


def _shared_ffn(p, x2d, cfg):
    h = x2d @ p["shared_w1"]["w"].astype(x2d.dtype)
    h = act_fn(cfg.act)(h)
    if cfg.gated_mlp:
        h = h * (x2d @ p["shared_w3"]["w"].astype(x2d.dtype))
    return h @ p["shared_w2"]["w"].astype(x2d.dtype)


# ---------------------------------------------------------------------------
# Dense (oracle / smoke) path
# ---------------------------------------------------------------------------
def moe_dense(p, x, cfg):
    """x: [B, S, d]. Runs every expert on every token."""
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    weights, ids, aux = _router(p, x2d, cfg)
    E = cfg.n_experts
    # combine weights as a dense [T, E] matrix (zero off the top-k)
    comb = jnp.zeros((x2d.shape[0], E), x2d.dtype)
    comb = comb.at[jnp.arange(x2d.shape[0])[:, None], ids].set(
        weights.astype(x2d.dtype))
    h = jnp.einsum("td,edf->tef", x2d, p["w1"].astype(x2d.dtype))
    h = act_fn(cfg.act)(h)
    if cfg.gated_mlp:
        h = h * jnp.einsum("td,edf->tef", x2d, p["w3"].astype(x2d.dtype))
    y_all = jnp.einsum("tef,efd->ted", h, p["w2"].astype(x2d.dtype))
    y = jnp.einsum("ted,te->td", y_all, comb)
    if cfg.n_shared_experts:
        y = y + _shared_ffn(p, x2d, cfg)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Capacity (production) path
# ---------------------------------------------------------------------------
def _positions_in_expert(ids_flat, n_experts):
    """pos[i] = |{j < i : ids[j] == ids[i]}| via stable sort (O(N log N) mem-lean,
    instead of a [N, E] one-hot cumsum)."""
    N = ids_flat.shape[0]
    order = jnp.argsort(ids_flat, stable=True)
    sorted_ids = ids_flat[order]
    idx = jnp.arange(N, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0))
    pos_sorted = idx - seg_start
    pos = jnp.zeros((N,), jnp.int32).at[order].set(pos_sorted)
    return pos


def moe_capacity(p, x, cfg, capacity=None):
    """x: [B, S, d]. Capacity-dispatch MoE; tokens over capacity are dropped
    (standard Switch semantics — their expert contribution is zero, residual
    stream still carries them)."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    x2d = x.reshape(T, d)
    weights, ids, aux = _router(p, x2d, cfg)

    if capacity is None:
        capacity = int(max(8, round(T * k / E * cfg.capacity_factor)))
    C = capacity

    ids_flat = ids.reshape(-1)                               # [T*k]
    w_flat = weights.reshape(-1)
    pos = _positions_in_expert(ids_flat, E)                  # [T*k]
    keep = pos < C

    # scatter tokens into [E*C, d]; dropped assignments go out-of-range (drop)
    slot = jnp.where(keep, ids_flat * C + pos, E * C)
    token_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    buf = jnp.zeros((E * C, d), x2d.dtype)
    buf = buf.at[slot].add(x2d[token_idx], mode="drop")
    buf = buf.reshape(E, C, d)
    buf = constrain(buf, "experts", None, None)

    out_buf = _expert_ffn(p, buf, cfg).reshape(E * C, d)

    # gather back per assignment, weight, combine over the k slots
    safe_slot = jnp.where(keep, slot, 0)
    y_assign = out_buf[safe_slot] * (w_flat * keep).astype(out_buf.dtype)[:, None]
    y = y_assign.reshape(T, k, d).sum(axis=1)
    if cfg.n_shared_experts:
        y = y + _shared_ffn(p, x2d, cfg)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel (EP) path: shard_map + all_to_all token routing
# ---------------------------------------------------------------------------
def moe_ep(p, x, cfg, capacity=None):
    """Expert-parallel MoE: tokens are ROUTED to the expert's owner shard
    with lax.all_to_all instead of scatter-adding into a global [E, C, d]
    capacity buffer (which the SPMD partitioner realizes as giant
    all-reduces over the data axis — measured 49 GiB/layer on the 1T
    config). Requires an active mesh_context whose mesh carries a
    ``model`` axis that divides n_experts; falls back to capacity
    dispatch otherwise.

    Collective cost per layer: 2 all_to_alls of [T_loc*k, d] tokens
    (+ the FSDP weight all-gather), vs all-reduces of [E, C, d].
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.api import current_ctx

    ctx = current_ctx()
    if ctx is None or "model" not in ctx.mesh.axis_names:
        return moe_capacity(p, x, cfg, capacity)
    mesh = ctx.mesh
    model_axis = "model"
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    n_shards = mesh.shape[model_axis]
    E, k = cfg.n_experts, cfg.top_k
    assert E % n_shards == 0, (E, n_shards)
    E_loc = E // n_shards
    B, S, d = x.shape
    T = B * S
    # per-device token count: batch is sharded over the data axes
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    T_loc = T // n_data
    # send capacity per (src shard -> dst shard) lane; k assignments per
    # token spread over n_shards lanes on average
    cap_send = capacity or int(max(8, round(
        T_loc * k / n_shards * cfg.capacity_factor)))
    C_loc = int(max(8, round(T_loc * n_data * k / E
                             * cfg.capacity_factor)))

    def local(router_w, w1, w2, w3, xl):
        # xl: [B_loc, S, d] local tokens; weights arrive as local shards:
        # w1 [E_loc, d/fsdp, f] -> all-gather the FSDP dim
        if data_axes:
            w1 = jax.lax.all_gather(w1, data_axes, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, data_axes, axis=2, tiled=True)
            if w3 is not None:
                w3 = jax.lax.all_gather(w3, data_axes, axis=1, tiled=True)
        x2d = xl.reshape(-1, d)                          # [T_loc, d]
        logits = x2d.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, k)           # [T_loc, k]
        weights = weights / jnp.sum(weights, -1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1), 0)
        aux = E * jnp.sum(jax.lax.pmean(me, data_axes + (model_axis,))
                          * jax.lax.pmean(ce, data_axes + (model_axis,))
                          ) * cfg.router_aux_loss

        ids_f = ids.reshape(-1)                          # [T_loc*k]
        w_f = weights.reshape(-1).astype(x2d.dtype)
        dst = ids_f // E_loc                             # target shard
        # slot within the (dst) send lane
        lane_pos = _positions_in_expert(dst, n_shards)
        keep = lane_pos < cap_send
        slot = jnp.where(keep, dst * cap_send + lane_pos,
                         n_shards * cap_send)
        tok = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), k)
        send = jnp.zeros((n_shards * cap_send, d), x2d.dtype)
        send = send.at[slot].add(x2d[tok], mode="drop")
        send_eid = jnp.full((n_shards * cap_send,), -1, jnp.int32)
        send_eid = send_eid.at[slot].set(ids_f % E_loc, mode="drop")
        send = send.reshape(n_shards, cap_send, d)
        send_eid = send_eid.reshape(n_shards, cap_send)
        # exchange over the model axis
        recv = jax.lax.all_to_all(send, model_axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, model_axis, 0, 0,
                                      tiled=False)
        rv = recv.reshape(-1, d)                         # [S*cap_send, d]
        re = recv_eid.reshape(-1)
        # local expert dispatch
        valid = re >= 0
        pos = _positions_in_expert(jnp.where(valid, re, E_loc), E_loc + 1)
        keep2 = valid & (pos < C_loc)
        slot2 = jnp.where(keep2, re * C_loc + pos, E_loc * C_loc)
        buf = jnp.zeros((E_loc * C_loc, d), x2d.dtype)
        buf = buf.at[slot2].add(rv, mode="drop")
        h = buf.reshape(E_loc, C_loc, d)
        a = jnp.einsum("ecd,edf->ecf", h, w1.astype(h.dtype))
        a = act_fn(cfg.act)(a)
        if w3 is not None:
            a = a * jnp.einsum("ecd,edf->ecf", h, w3.astype(h.dtype))
        out = jnp.einsum("ecf,efd->ecd", a, w2.astype(h.dtype))
        out = out.reshape(E_loc * C_loc, d)
        # gather back per received slot, return to sender
        back = jnp.where(keep2[:, None], out[jnp.where(keep2, slot2, 0)],
                         0.0)
        back = back.reshape(n_shards, cap_send, d)
        ret = jax.lax.all_to_all(back, model_axis, 0, 0, tiled=False)
        ret = ret.reshape(-1, d)                         # [n_shards*cap, d]
        safe = jnp.where(keep, slot, 0)
        y_asn = jnp.where(keep[:, None], ret[safe], 0.0) \
            * w_f[:, None]
        y = jax.ops.segment_sum(y_asn, tok, num_segments=T_loc)
        return y.reshape(xl.shape).astype(xl.dtype), aux

    dp = P(data_axes if len(data_axes) > 1 else (data_axes[0]
                                                 if data_axes else None))
    x_spec = P(dp[0] if data_axes else None, None, None)
    w1_spec = P(model_axis, dp[0] if data_axes else None, None)
    w2_spec = P(model_axis, None, dp[0] if data_axes else None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), w1_spec, w2_spec,
                  w1_spec if cfg.gated_mlp else P(None), x_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    w3 = p.get("w3") if cfg.gated_mlp else None
    y, aux = fn(p["router"]["w"], p["w1"], p["w2"], w3, x)
    if cfg.n_shared_experts:
        x2d = x.reshape(-1, d)
        y = y + _shared_ffn(p, x2d, cfg).reshape(x.shape)
    return y, aux


def moe_apply(p, x, cfg, impl="capacity"):
    if impl == "dense":
        return moe_dense(p, x, cfg)
    if impl == "ep":
        return moe_ep(p, x, cfg)
    return moe_capacity(p, x, cfg)
