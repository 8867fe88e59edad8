"""Mixture-of-Experts FFN with sort-based static-shape routing.

Two dispatch modes, selectable per config:

  * ``tp``  — experts sharded over the ``model`` axis; every model shard
    routes *all* of its (data-sharded) tokens to its local expert subset
    and partial outputs are summed with a psum over ``model``. No token
    ever crosses the data/pod axes. This is the robust default and what
    the dry-run lowers.

  * ``monitor_a2a`` — the paper-T3 integration: experts sharded over the
    *combined* (pod, data) token axes; tokens travel to expert owners via
    the two-phase hierarchical all-to-all (intra-pod collection -> mirror
    -group exchange), exactly the monitor forwarding pattern. Used by the
    §Perf hillclimb of the MoE cells.

Routing is sort-based with per-shard static capacity (tokens above
capacity are dropped, standard GShard semantics; capacity_factor config).
Router in fp32, aux load-balancing loss (Switch-style) returned to the
caller.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp


Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    d_ff: int          # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    mlp_kind: str = "swiglu"


def init_moe(key, dims: MoEDims, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f, e = dims.d_model, dims.d_ff, dims.n_experts
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(k1, (d, e)) * s_in).astype(jnp.float32),
        "w_in": (jax.random.normal(k2, (e, d, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k3, (e, f, d)) * s_out).astype(dtype),
    }
    if dims.mlp_kind == "swiglu":
        p["w_gate"] = (jax.random.normal(k4, (e, d, f)) * s_in).astype(dtype)
    return p


def _route(logits: jax.Array, dims: MoEDims, capacity: int):
    """Sort-based static routing. logits [T, E] fp32.

    Returns (slot [T*k] target slot in [E*C] or E*C when dropped,
             gate [T*k] fp32, aux_loss scalar).
    """
    t, e = logits.shape
    k = dims.top_k
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)              # [T, k]
    gate = gate / jnp.clip(jnp.sum(gate, -1, keepdims=True), 1e-9)
    flat_e = idx.reshape(-1).astype(jnp.int32)       # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position within expert group
    start = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=jnp.int32))
    pos = jnp.arange(t * k, dtype=jnp.int32) - start[sorted_e]
    keep = pos < capacity
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos, e * capacity)
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(slot_sorted)
    # aux loss: Switch load-balance (fraction routed x mean prob)
    top1 = idx[:, 0]
    frac = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_p)
    return slot, gate.reshape(-1), aux


def _expert_mlp(p: Params, x: jax.Array, dims: MoEDims) -> jax.Array:
    """x: [E, C, D] -> [E, C, D] via per-expert FFN (einsum over stacked w)."""
    if dims.mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"])) * \
            jnp.einsum("ecd,edf->ecf", x, p["w_in"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["w_in"]))
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"]).astype(x.dtype)


def moe_ffn(p: Params, x: jax.Array, dims: MoEDims) -> tuple[jax.Array, jax.Array]:
    """Dense (sharding-agnostic) MoE FFN: x [B, S, D] -> ([B, S, D], aux).

    Under pjit, tokens stay data-sharded; the expert einsums shard over the
    ``model`` axis via the stacked-weight shardings (E-dim sharded) and XLA
    inserts the reduce over experts. Capacity is computed from the *global*
    token count — per-shard routing variance is absorbed by the factor.
    """
    b, s, d = x.shape
    t = b * s
    e, k = dims.n_experts, dims.top_k
    capacity = max(1, int(t * k * dims.capacity_factor / e))
    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    slot, gate, aux = _route(logits, dims, capacity)

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    tok_of_pair = jnp.arange(t * k, dtype=jnp.int32) // k
    buf = buf.at[slot].add(xf[tok_of_pair])          # dropped -> slot E*C
    expert_in = buf[:-1].reshape(e, capacity, d)
    expert_out = _expert_mlp(p, expert_in, dims).reshape(e * capacity, d)
    expert_out = jnp.concatenate([expert_out, jnp.zeros((1, d), x.dtype)])
    out_pairs = expert_out[slot] * gate[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(out_pairs, tok_of_pair, num_segments=t)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn_local_tp(
    p: Params,
    x: jax.Array,          # [B_loc, S, D] — this shard's tokens
    dims: MoEDims,
    *,
    model_axis: str = "model",
) -> tuple[jax.Array, jax.Array]:
    """§Perf variant "local_tp": run *inside* shard_map.

    Hypothesis (EXPERIMENTS.md §Perf cell A): the baseline's GLOBAL
    argsort over [T*k] routed pairs is what blows the collective term —
    XLA lowers a cross-device sort as O(log^2) all-to-all rounds. Routing
    is per-token; nothing about it needs to be global. Here every shard
    routes its LOCAL tokens, keeps the (token, expert) pairs whose expert
    lives on this model shard (experts block-sharded over ``model``), and
    the only collective left is one psum over ``model`` of the [T_loc, D]
    output partials — the Megatron-style TP combine.
    """
    from jax import lax

    m = lax.axis_size(model_axis)
    me = lax.axis_index(model_axis)
    b, s, d = x.shape
    t = b * s
    e, k = dims.n_experts, dims.top_k
    assert e % m == 0, (e, m)
    e_loc = e // m
    my_first = me * e_loc

    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"]          # router replicated
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                    # [T, k]
    gate = gate / jnp.clip(jnp.sum(gate, -1, keepdims=True), 1e-9)

    # keep only pairs owned by this shard; local sort-based dispatch
    flat_e = idx.reshape(-1).astype(jnp.int32)
    mine = (flat_e >= my_first) & (flat_e < my_first + e_loc)
    local_e = jnp.where(mine, flat_e - my_first, e_loc)    # e_loc = drop
    capacity = max(1, int(t * k * dims.capacity_factor / e))
    order = jnp.argsort(local_e, stable=True)
    sorted_e = local_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1, dtype=jnp.int32))
    pos = jnp.arange(t * k, dtype=jnp.int32) - start[jnp.clip(sorted_e, 0, e_loc)]
    keep = (sorted_e < e_loc) & (pos < capacity)
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos, e_loc * capacity)
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(slot_sorted)
    tok_of_pair = jnp.arange(t * k, dtype=jnp.int32) // k

    buf = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
    buf = buf.at[slot].add(xf[tok_of_pair])
    # inside shard_map the stacked expert weights arrive PRE-SHARDED over
    # the expert dim: p["w_in"] is [e_loc, d, f] on this shard.
    expert_in = buf[:-1].reshape(e_loc, capacity, d)
    expert_out = _expert_mlp(p, expert_in, dims).reshape(e_loc * capacity, d)
    expert_out = jnp.concatenate([expert_out, jnp.zeros((1, d), x.dtype)])
    out_pairs = expert_out[slot] * gate.reshape(-1)[:, None].astype(x.dtype)
    partial = jax.ops.segment_sum(out_pairs, tok_of_pair, num_segments=t)
    out = lax.psum(partial, model_axis)                    # the ONLY collective
    aux = e * jnp.sum(
        jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0)
        * jnp.mean(probs, 0))
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn_monitor(
    p: Params,
    x: jax.Array,
    dims: MoEDims,
    *,
    group_axis: str,
    member_axis: str,
) -> tuple[jax.Array, jax.Array]:
    """T3 dispatch: run *inside* shard_map over (group, member) token axes.

    Experts are partitioned over the flattened (group, member) device space
    (owner = expert % P — the cyclic heavy-vertex rule, eq. 3). Each shard
    routes its local tokens, buckets them by owner device, and the buckets
    move through the two-phase hierarchical all-to-all; expert outputs
    return the same way.
    """
    from jax import lax
    from repro.comms.hierarchical import hierarchical_all_to_all

    g = lax.axis_size(group_axis)
    m = lax.axis_size(member_axis)
    pdev = g * m
    b, s, d = x.shape
    t = b * s
    e, k = dims.n_experts, dims.top_k
    assert e % pdev == 0, (e, pdev)
    e_loc = e // pdev
    # local routing
    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    cap_dev = max(1, int(t * k * dims.capacity_factor / pdev))
    # treat each *device* as a super-expert bucket: owner(expert) = e % P
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.clip(jnp.sum(gate, -1, keepdims=True), 1e-9)
    owner = (idx % pdev).astype(jnp.int32)           # [T, k]
    flat_o = owner.reshape(-1)
    order = jnp.argsort(flat_o, stable=True)
    sorted_o = flat_o[order]
    start = jnp.searchsorted(sorted_o, jnp.arange(pdev, dtype=jnp.int32))
    pos = jnp.arange(t * k, dtype=jnp.int32) - start[sorted_o]
    keep = pos < cap_dev
    slot_sorted = jnp.where(keep, sorted_o * cap_dev + pos, pdev * cap_dev)
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(slot_sorted)
    tok_of_pair = jnp.arange(t * k, dtype=jnp.int32) // k

    send = jnp.zeros((pdev * cap_dev + 1, d), x.dtype)
    send = send.at[slot].add(xf[tok_of_pair])
    send_e = jnp.zeros((pdev * cap_dev + 1,), jnp.int32)
    send_e = send_e.at[slot].max(idx.reshape(-1) // pdev)  # local expert idx at owner
    payload = send[:-1]                                    # [P*C, D]
    eidx = send_e[:-1]

    # --- monitor exchange: tokens to owners -------------------------------
    recv = hierarchical_all_to_all(payload, group_axis, member_axis)
    recv_e = hierarchical_all_to_all(eidx[:, None], group_axis, member_axis)[:, 0]
    # recv: [P*C, D] tokens destined to local experts, any source device.
    onehot = jax.nn.one_hot(recv_e, e_loc, dtype=recv.dtype)   # [P*C, e_loc]
    # per-local-expert dense compute via masked einsum (cap_dev rows/device).
    # Expert id e lives on owner e % P with local index e // P (cyclic rule,
    # paper eq. 3) -> stacked weights factor as [e_loc, P, ...].
    me = lax.axis_index(group_axis) * m + lax.axis_index(member_axis)

    def local_w(wall, trailing):
        wv = wall.reshape((e_loc, pdev) + trailing)
        return lax.dynamic_slice_in_dim(wv, me, 1, 1)[:, 0]

    f = p["w_in"].shape[-1]
    wi = local_w(p["w_in"], (d, f))
    wo = local_w(p["w_out"], (f, d))
    h = jnp.einsum("td,edf,te->tf", recv, wi, onehot,
                   preferred_element_type=jnp.float32)
    if dims.mlp_kind == "swiglu":
        wg = local_w(p["w_gate"], (d, f))
        hg = jnp.einsum("td,edf,te->tf", recv, wg, onehot,
                        preferred_element_type=jnp.float32)
        h = jax.nn.silu(hg) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("tf,efd,te->td", h.astype(x.dtype), wo, onehot,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    # --- return trip -------------------------------------------------------
    back = hierarchical_all_to_all(y, group_axis, member_axis)
    back = jnp.concatenate([back, jnp.zeros((1, d), x.dtype)])
    out_pairs = back[slot] * gate.reshape(-1)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(out_pairs, tok_of_pair, num_segments=t)
    aux = e * jnp.sum(
        jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0)
        * jnp.mean(probs, 0))
    return out.reshape(b, s, d), aux
