"""Decoder-only transformer LM (dense + MoE) with scan-over-layers.

Covers the five assigned LM architectures:
  starcoder2-15b  GQA(48q/4kv) + GELU MLP + layernorm
  minicpm-2b      MHA(36)      + SwiGLU   + rmsnorm (WSD schedule in optim)
  olmo-1b         MHA(16)      + SwiGLU   + non-parametric LN
  moonshot-v1-16b-a3b  GQA + MoE 64e top-6 (shared dense path optional)
  granite-moe-1b-a400m GQA(16q/8kv) + MoE 32e top-8

Layer parameters are stacked ``[L, ...]`` and the body is a single
``lax.scan`` (keeps HLO size O(1) in depth — critical for the 512-device
dry-run compiles) with optional ``jax.checkpoint`` remat.

Sharding: a ``ShardingPolicy`` names the mesh axes; activations carry
``with_sharding_constraint`` hints — batch over (pod, data), optional
Megatron-style sequence sharding over ``model`` between blocks.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import layers as L
from repro.models import moe as M

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    norm: str = "rmsnorm"            # rmsnorm | layernorm | nonparametric_ln
    mlp: str = "swiglu"              # swiglu | gelu
    rope_theta: float = 10000.0
    tied_embeddings: bool = True
    # MoE (None => dense)
    n_experts: Optional[int] = None
    top_k: Optional[int] = None
    capacity_factor: float = 1.25
    # serving
    window: Optional[int] = None     # sliding-window mode (beyond-spec)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts is not None

    @property
    def attn_dims(self) -> L.AttnDims:
        return L.AttnDims(self.d_model, self.n_heads, self.n_kv_heads, self.head_dim)

    @property
    def moe_dims(self) -> M.MoEDims:
        return M.MoEDims(self.d_model, self.d_ff, self.n_experts, self.top_k,
                         self.capacity_factor, self.mlp)

    def param_count(self) -> int:
        d, f, h, hk, dh = self.d_model, self.d_ff, self.n_heads, self.n_kv_heads, self.head_dim
        attn = d * h * dh + 2 * d * hk * dh + h * dh * d
        if self.is_moe:
            per_ff = self.n_experts * (d * f * (3 if self.mlp == "swiglu" else 2))
            per_ff += d * self.n_experts
        else:
            per_ff = d * f * (3 if self.mlp == "swiglu" else 2)
        emb = self.vocab * d * (1 if self.tied_embeddings else 2)
        return self.n_layers * (attn + per_ff) + emb

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of n_experts)."""
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        attn = d * self.n_heads * self.head_dim + 2 * d * self.n_kv_heads * self.head_dim \
            + self.n_heads * self.head_dim * d
        ff = self.top_k * d * f * (3 if self.mlp == "swiglu" else 2) + d * self.n_experts
        emb = self.vocab * d * (1 if self.tied_embeddings else 2)
        return self.n_layers * (attn + ff) + emb


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    mesh: Optional[Mesh] = None
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"
    sequence_sharded: bool = False   # Megatron-SP style between blocks
    remat: bool = True
    # dry-run sets True: XLA cost_analysis counts while-loop bodies ONCE,
    # so roofline lowering unrolls the layer scan (EXPERIMENTS.md §Dry-run)
    unroll_layers: bool = False
    # MoE dispatch: "dense" (pjit sort-based, baseline) | "local_tp"
    # (§Perf cell A: per-shard routing + psum(model) combine via shard_map)
    moe_mode: str = "dense"
    # exact query-chunked attention: caps score memory (§Perf cell D)
    q_chunk: Optional[int] = None

    def ns(self, *spec) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*spec))

    def constrain(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.ns(*spec))


REPLICATED = ShardingPolicy()


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(key, cfg: LMConfig, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    d = cfg.d_model
    emb = (jax.random.normal(k_emb, (cfg.vocab, d)) * 0.02).astype(dtype)

    def one_layer(k):
        k1, k2 = jax.random.split(k)
        p = {
            "attn": L.init_attention(k1, cfg.attn_dims, dtype),
            "norm1": L.init_norm(cfg.norm, d),
            "norm2": L.init_norm(cfg.norm, d),
        }
        if cfg.is_moe:
            p["moe"] = M.init_moe(k2, cfg.moe_dims, dtype)
        else:
            p["mlp"] = L.init_mlp(k2, d, cfg.d_ff, cfg.mlp, dtype)
        return p

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(one_layer)(layer_keys)
    params = {
        "embed": emb,
        "layers": stacked,
        "final_norm": L.init_norm(cfg.norm, d),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (d, cfg.vocab)) / math.sqrt(d)
        ).astype(dtype)
    return params


def param_shardings(cfg: LMConfig, policy: ShardingPolicy) -> Params:
    """NamedSharding tree matching init_params (Megatron TP layout)."""
    mp = policy.model_axis
    ns = policy.ns

    attn = {"wq": ns(None, None, mp), "wk": ns(None, None, mp),
            "wv": ns(None, None, mp), "wo": ns(None, mp, None)}
    norm = {"scale": ns(None, None)} if cfg.norm == "rmsnorm" else (
        {"scale": ns(None, None), "bias": ns(None, None)}
        if cfg.norm == "layernorm" else {})
    layer = {"attn": attn, "norm1": dict(norm), "norm2": dict(norm)}
    if cfg.is_moe:
        moe = {"router": ns(None, None, None),
               "w_in": ns(None, mp, None, None),
               "w_out": ns(None, mp, None, None)}
        if cfg.mlp == "swiglu":
            moe["w_gate"] = ns(None, mp, None, None)
        layer["moe"] = moe
    else:
        mlp = {"w_in": ns(None, None, mp), "w_out": ns(None, mp, None)}
        if cfg.mlp == "swiglu":
            mlp["w_gate"] = ns(None, None, mp)
        layer["mlp"] = mlp
    out = {
        "embed": ns(mp, None),
        "layers": layer,
        "final_norm": {"scale": ns(None)} if cfg.norm == "rmsnorm" else (
            {"scale": ns(None), "bias": ns(None)} if cfg.norm == "layernorm" else {}),
    }
    if not cfg.tied_embeddings:
        out["lm_head"] = ns(None, mp)
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _block(cfg: LMConfig, policy: ShardingPolicy, x, lp, positions):
    ba = policy.batch_axes
    mp = policy.model_axis
    if policy.sequence_sharded:
        x = policy.constrain(x, ba, mp, None)
    h = L.apply_norm(cfg.norm, x, lp["norm1"])
    h = L.attention(lp["attn"], h, cfg.attn_dims,
                    positions=positions, rope_theta=cfg.rope_theta,
                    window=cfg.window, q_chunk=policy.q_chunk,
                    unroll_chunks=policy.unroll_layers)
    x = x + h
    h = L.apply_norm(cfg.norm, x, lp["norm2"])
    if cfg.is_moe:
        if policy.moe_mode == "local_tp" and policy.mesh is not None:
            h, aux = _moe_local_tp_sharded(cfg, policy, h, lp["moe"])
        elif policy.moe_mode == "monitor_a2a" and policy.mesh is not None:
            h, aux = _moe_monitor_sharded(cfg, policy, h, lp["moe"])
        else:
            h, aux = M.moe_ffn(lp["moe"], h, cfg.moe_dims)
    else:
        h, aux = L.mlp(lp["mlp"], h, cfg.mlp), jnp.float32(0)
    x = x + h
    x = policy.constrain(x, ba, None, None)
    return x, aux


def _moe_monitor_sharded(cfg: LMConfig, policy: ShardingPolicy, h, moe_p):
    """§Perf cell A variant "monitor_a2a": tokens travel to expert owners
    through the two-phase hierarchical (monitor) all-to-all over the
    (pod, data) axes — the paper-T3 dispatch. Requires >= 2 batch axes."""
    mesh = policy.mesh
    ba = policy.batch_axes
    assert len(ba) >= 2, "monitor_a2a needs (pod, data) batch axes"
    group_axis, member_axis = ba[0], ba[-1]
    espec = {"router": P(), "w_in": P(), "w_out": P()}
    if "w_gate" in moe_p:
        espec["w_gate"] = P()

    def local(hh, pp):
        out, aux = M.moe_ffn_monitor(pp, hh, cfg.moe_dims,
                                     group_axis=group_axis,
                                     member_axis=member_axis)
        return out, jax.lax.pmean(aux, ba)

    mp = policy.model_axis
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), espec),
        out_specs=(P(ba, None, None), P()),
    )(h, moe_p)
    return out, aux


def _moe_local_tp_sharded(cfg: LMConfig, policy: ShardingPolicy, h, moe_p):
    """shard_map wrapper for the local_tp MoE dispatch (§Perf cell A)."""
    mesh = policy.mesh
    ba = policy.batch_axes
    mp = policy.model_axis
    espec = {"router": P(), "w_in": P(mp, None, None),
             "w_out": P(mp, None, None)}
    if "w_gate" in moe_p:
        espec["w_gate"] = P(mp, None, None)

    def local(hh, pp):
        out, aux = M.moe_ffn_local_tp(pp, hh, cfg.moe_dims, model_axis=mp)
        # aux is invariant along model (router replicated); mean over batch
        return out, jax.lax.pmean(aux, ba)

    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), espec),
        out_specs=(P(ba, None, None), P()),
    )(h, moe_p)
    return out, aux


def forward(params: Params, tokens: jax.Array, cfg: LMConfig,
            policy: ShardingPolicy = REPLICATED) -> tuple[jax.Array, jax.Array]:
    """tokens [B, S] -> (logits [B, S, V], aux_loss)."""
    b, s = tokens.shape
    x = params["embed"][tokens]
    x = policy.constrain(x, policy.batch_axes, None, None)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    block = partial(_block, cfg, policy)
    if policy.remat:
        block = jax.checkpoint(block, static_argnums=())

    def scan_fn(x, lp):
        x, aux = block(x, lp, positions)
        return x, aux

    x, auxes = jax.lax.scan(scan_fn, x, params["layers"],
                            unroll=cfg.n_layers if policy.unroll_layers else 1)
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    logits = policy.constrain(logits, policy.batch_axes, None, policy.model_axis)
    return logits, jnp.sum(auxes)


# ---------------------------------------------------------------------------
# Decode (serve_step): one token against a KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: LMConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, s_max, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_shardings(cfg: LMConfig, policy: ShardingPolicy,
                    shard_seq: bool = False):
    """KV cache sharded: batch over (pod+data), kv-heads over model.

    When ``shard_seq`` (long-context mode) the sequence dim also shards
    over ``model`` — with few KV heads (GQA) heads alone can't fill the
    mesh axis; see configs for which cells enable it."""
    ba = policy.batch_axes
    mp = policy.model_axis
    if shard_seq:
        s = policy.ns(None, ba, mp, None, None)
    else:
        s = policy.ns(None, ba, None, mp, None)
    return {"k": s, "v": s}


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                cfg: LMConfig, policy: ShardingPolicy = REPLICATED):
    """tokens [B, 1] + cache @ pos -> (logits [B, V], new cache)."""
    b = tokens.shape[0]
    x = params["embed"][tokens]           # [B, 1, D]
    x = policy.constrain(x, policy.batch_axes, None, None)

    def scan_fn(x, inputs):
        lp, ck, cv = inputs
        h = L.apply_norm(cfg.norm, x, lp["norm1"])
        h, ck, cv = L.decode_attention(
            lp["attn"], h, ck, cv, pos, cfg.attn_dims,
            rope_theta=cfg.rope_theta, window=cfg.window)
        x = x + h
        h = L.apply_norm(cfg.norm, x, lp["norm2"])
        if cfg.is_moe:
            h, _ = M.moe_ffn(lp["moe"], h, cfg.moe_dims)
        else:
            h = L.mlp(lp["mlp"], h, cfg.mlp)
        return x + h, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        scan_fn, x, (params["layers"], cache["k"], cache["v"]),
        unroll=cfg.n_layers if policy.unroll_layers else 1)
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)[:, 0]
    return logits, {"k": new_k, "v": new_v}
