"""Distributed full-graph GNN layers (§Perf cell B, paper T2+T3 for GNNs).

Baseline (gnn_cell "baseline"): pjit with nodes/edges sharded and XLA
free to choose — it materializes edge-level all-to-alls (ogb_products:
collective term 0.21 s/step vs 9e-6 s compute).

Variant "owner_gather" (B1): shard_map layer with
  * nodes owner-partitioned [N_loc, F] (contiguous blocks);
  * edges partitioned by DST owner (each device aggregates into its own
    rows — nothing is scattered remotely);
  * ONE hierarchical (monitor, T3) all-gather of node features per layer
    — the only collective; link bytes = N x F x 4 x (P-1)/P per device
    instead of per-edge traffic.

Variant "owner_gather_bf16" (B3): same, features cast to bf16 for the
gather leg only (the activation analogue of the gradient-compression
trick) — halves the collective term; fp32 restored for the local math.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.comms.hierarchical import hierarchical_all_gather
from repro.train.train_step import softmax_xent


def sage_layer_local(p, h_full, h_own, src, dst_local, valid, n_loc, last):
    """One SAGE layer on the owner shard.

    h_full: [N, F] gathered features; h_own: [N_loc, F] owned rows;
    src: global ids of edge sources; dst_local: local row of edge target.
    """
    f = h_full.shape[1]
    hs = jnp.concatenate([h_full, jnp.zeros((1, f), h_full.dtype)])
    n_glob = h_full.shape[0]
    s = jnp.where(valid, src, n_glob)
    seg = jnp.where(valid, dst_local, n_loc)
    msum = jax.ops.segment_sum(hs[s], seg, num_segments=n_loc + 1)[:n_loc]
    cnt = jax.ops.segment_sum(valid.astype(h_full.dtype), seg,
                              num_segments=n_loc + 1)[:n_loc]
    mean = msum / jnp.clip(cnt[:, None], 1.0)
    out = h_own @ p["w_self"] + mean @ p["w_neigh"]
    return out if last else jax.nn.relu(out)


def make_sage_dist_step(cfg, opt, mesh: Mesh, axes: tuple[str, ...],
                        n_nodes: int, *, hierarchical: bool = True,
                        gather_dtype=jnp.float32):
    """Owner-partitioned full-graph SAGE train step (inside shard_map).

    ``axes`` — every mesh axis, flattened device order = owner order.
    Inputs (per the cell plan): feats [N, F] sharded dim0; edge arrays
    sharded dim0 (pre-partitioned by dst owner, dst_local row ids);
    labels [N] sharded dim0.
    """
    gaxes, maxes = axes[:-1], axes[-1:]

    def local_loss(params, feats, src, dst_local, valid, labels):
        n_loc = feats.shape[0]
        # B3: the whole layer pipeline runs in gather_dtype (bf16 halves
        # every collective byte). NOTE a naive cast-gather-castback gets
        # CANCELLED by XLA's algebraic simplifier (verified — see
        # EXPERIMENTS.md §Perf cell B iteration 2): the low precision must
        # be load-bearing through the layer math.
        h = feats.astype(gather_dtype)
        for i, lp in enumerate(params["layers"]):
            last = i == cfg.n_layers - 1
            # T3: monitor-hierarchical gather of the CURRENT layer feats
            if hierarchical:
                h_full = hierarchical_all_gather(h, gaxes, maxes)
            else:
                h_full = lax.all_gather(h, axes, axis=0, tiled=True)
            lpd = jax.tree.map(lambda w: w.astype(gather_dtype), lp)
            h = sage_layer_local(lpd, h_full, h, src, dst_local, valid,
                                 n_loc, last)
        nll = softmax_xent(h.astype(jnp.float32), labels)
        return lax.pmean(nll, axes)

    def step(params, opt_state, feats, src, dst_local, valid, labels):
        def shard_loss(feats, src, dst_local, valid, labels, params):
            loss, grads = jax.value_and_grad(
                lambda p: local_loss(p, feats, src, dst_local, valid, labels)
            )(params)
            grads = jax.tree.map(lambda g: lax.psum(g, axes), grads)
            return loss, grads

        sharded = jax.shard_map(
            shard_loss, mesh=mesh,
            in_specs=(P(axes, None), P(axes), P(axes), P(axes), P(axes), P()),
            out_specs=(P(), P()),
        )
        loss, grads = sharded(feats, src, dst_local, valid, labels, params)
        new_params, new_state = opt.update(grads, opt_state, params)
        return new_params, new_state, loss

    return step
