"""Mixture-of-Experts FFN: token-choice top-k routing with GShard-style
*group-local* capacity dispatch (groups = batch rows), scatter/gather based so
the (tokens, experts, capacity) dispatch tensor never materializes.

Group-locality matters under SPMD: the position-in-expert cumsum runs along
the *unsharded* (seq*k) dim, so GSPMD never has to do a cross-shard prefix
sum; the only collective introduced is the (group-sharded -> expert-sharded)
resharding around the expert einsums, i.e. the all-to-all an MoE layer is
supposed to have.

Supports Mixtral (8e top-2, softmax router + Switch aux loss) and DeepSeek-V3
(256 routed + 1 shared, top-8, sigmoid router with aux-free bias balancing,
routed_scaling_factor). Expert weights carry the "expert" logical axis →
expert parallelism over the "model" mesh axis; when |experts| < |axis| the
rules fall back to TP over the expert mlp dim (see parallel/sharding.py).

Jamba (16e top-2, softmax router, weights not renormalised) runs the
dropless layer instead (``dropless_moe``, chosen by ``MoEConfig.dropless``
in transformer.py): told which of the router's experts it holds, it
computes their part of the result for every token routed to them and
drops none.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.common import ParamSpec
from repro.parallel.sharding import with_logical_constraint


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    e = cfg.moe
    ne, ns, f = e.num_experts, e.num_shared_experts, e.expert_d_ff
    lo, hi = e.held_range()
    nh = hi - lo                    # the router spans all ne; weights nh
    specs = {
        "router": ParamSpec((d, ne), ("embed", "expert"), "scaled",
                            dtype=jnp.float32),
        "w_gate": ParamSpec((nh, d, f), ("expert", "expert_embed", "expert_mlp"), "scaled"),
        "w_up": ParamSpec((nh, d, f), ("expert", "expert_embed", "expert_mlp"), "scaled"),
        "w_down": ParamSpec((nh, f, d), ("expert", "expert_mlp", "expert_embed"), "scaled"),
    }
    if e.router_aux_free:
        specs["router_bias"] = ParamSpec((ne,), ("expert",), "zeros",
                                         dtype=jnp.float32)
    if ns:
        specs["shared_gate"] = ParamSpec((d, ns * f), ("embed", "mlp"), "scaled")
        specs["shared_up"] = ParamSpec((d, ns * f), ("embed", "mlp"), "scaled")
        specs["shared_down"] = ParamSpec((ns * f, d), ("mlp", "embed"), "scaled")
    return specs


def _route(params, x: jax.Array, e: MoEConfig, aux: bool = True):
    """x: (B, S, D) -> weights (B,S,K), idx (B,S,K) int32, aux scalar (0
    where `aux` is off)."""
    # matmul in the activation dtype, softmax/sigmoid in f32: an f32 input
    # here makes grad_x an f32 (B,S,D) tensor that must be all-reduced over
    # the expert axis — measured at ~40% of deepseek-v3's train collectives
    logits = jnp.einsum("bsd,de->bse", x,
                        params["router"].astype(x.dtype)).astype(jnp.float32)
    if e.router_aux_free:
        scores = jax.nn.sigmoid(logits)
        sel = scores + params["router_bias"][None, None, :]
        _, idx = jax.lax.top_k(sel, e.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        w = w * e.router_scale
        aux = jnp.float32(0.0)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, e.top_k)
        if e.renormalize:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        if not aux:
            return w, idx, jnp.float32(0.0)
        # Switch-style load-balance loss (per group, then averaged)
        me = probs.mean(axis=(0, 1))                       # (E,)
        fe = jax.nn.one_hot(idx[..., 0], e.num_experts,
                            dtype=jnp.float32).mean(axis=(0, 1))
        aux = e.num_experts * jnp.sum(me * fe)
    return w, idx, aux


def _positions_in_expert(flat: jax.Array) -> jax.Array:
    """flat: (G, T) expert ids -> occurrence rank of each id at each slot.

    Stable-sort the ids; within the sorted order an id's occurrences are a
    contiguous run, so rank = index - run_start, where run_start propagates
    by a max-scan. Ranks scatter back through the sort permutation. All
    buffers stay (G, T) int32.
    """
    g, t = flat.shape
    order = jnp.argsort(flat, axis=1, stable=True)         # (G, T)
    sorted_e = jnp.take_along_axis(flat, order, axis=1)
    iota = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (g, t))
    is_start = jnp.concatenate(
        [jnp.ones((g, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, iota, 0), axis=1)
    pos_sorted = iota - run_start
    pos = jnp.zeros_like(flat)
    pos = jax.vmap(lambda p, o, v: p.at[o].set(v))(pos, order, pos_sorted)
    return pos


def dropless_moe(params, x: jax.Array, cfg: ModelConfig, aux: bool = True):
    """Dropless top-k MoE over the experts this layer holds.

    Every token is routed over all of the router's num_experts; each
    assignment to a held expert [lo, hi) is computed, however many land on
    one expert, and one to an expert held elsewhere adds nothing here (that
    expert's chip adds it).  The assignments are sorted by expert and the
    held experts' SwiGLUs run as grouped matmuls (jax.lax.ragged_dot) over
    the sorted rows, so the work follows the routed tokens and no
    (experts, capacity) buffer is built.

    x: (B, S, D) -> (out (B,S,D), aux_loss (0 where `aux` is off), counts
    (nh,) int32: the assignments routed to each held expert)."""
    e = cfg.moe
    b, s, d = x.shape
    k = e.top_k
    lo, hi = e.held_range()
    nh = hi - lo
    w, idx, aux = _route(params, x, e, aux)
    n = b * s * k
    local = idx.reshape(n) - lo
    mine = (local >= 0) & (local < nh)
    group = jnp.where(mine, local, nh)        # held elsewhere: sorted last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=nh + 1)[:nh].astype(jnp.int32)
    tok = order // k                          # each sorted row's token
    xs = jnp.take(x.reshape(b * s, d), tok, axis=0)
    g = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jax.lax.ragged_dot(h, params["w_down"], sizes)
    # rows past the held groups are no group's: their output is not
    # defined, so they are masked, not merely weighted by zero
    valid = jnp.arange(n) < sizes.sum()
    wt = jnp.where(mine, w.reshape(n), 0.0)[order]
    y = jnp.where(valid[:, None], y.astype(jnp.float32) * wt[:, None], 0.0)
    out = jnp.zeros((b * s, d), jnp.float32).at[tok].add(y)
    return out.astype(x.dtype).reshape(b, s, d), aux, sizes


def moe_ffn(params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B,S,D), aux_loss)."""
    e = cfg.moe
    b0, s0, d = x.shape
    k, ne = e.top_k, e.num_experts

    w, idx, aux = _route(params, x, e)

    # decode-time regrouping: with s*k << num_experts the per-row capacity
    # buffer is ~(ne/ (s*k))x empty — merge rows into fewer, fuller groups
    # (target ~2*ne dispatched slots per group) before capacity assignment.
    b, s = b0, s0
    if s0 * k < ne and b0 > 1:
        tpg = max(1, 2 * ne // k)               # tokens per group
        g = max(1, (b0 * s0) // tpg)
        while (b0 * s0) % g:
            g -= 1
        b, s = g, b0 * s0 // g
        x = x.reshape(b, s, d)
        w = w.reshape(b, s, k)
        idx = idx.reshape(b, s, k)
    cap = max(1, int(e.capacity_factor * s * k / ne))

    # --- group-local (per batch row) position-in-expert, sort-based ---
    # (an earlier one-hot+cumsum formulation materialized a (B, S*K, E)
    # int32 tensor per layer — ~540MB/device/layer on deepseek-v3; the sort
    # keeps everything (B, S*K) int32.)
    flat = idx.reshape(b, s * k)                           # (B, S*K)
    pos = _positions_in_expert(flat)
    keep = pos < cap
    dst = jnp.where(keep, flat * cap + pos, ne * cap)      # overflow -> slot E*cap

    # --- scatter tokens into (B, E, C, D) ---
    # vmapped 1-D scatter per group: lowers to a scatter with operand batching
    # dims, which GSPMD partitions along the (sharded) group axis. A flat 2-D
    # index scatter instead makes GSPMD replicate the whole dispatch tensor
    # (observed: a 224 GiB f32 all-gather on deepseek-v3).
    wr = w.reshape(b, s * k).astype(x.dtype)

    def scatter_group(xg, dstg):
        xe = jnp.repeat(xg, k, axis=0)                     # (S*K, D)
        return jnp.zeros((ne * cap + 1, d), x.dtype).at[dstg].add(xe)

    buf = jax.vmap(scatter_group)(x, dst)
    buf = buf[:, :-1].reshape(b, ne, cap, d)
    # two-stage sharding: the scatter itself must stay sharded on its GROUP
    # (batching) dim — GSPMD replicates data-dependent scatter outputs
    # resharded on other dims. The *2 axes then move the buffer to the
    # expert-parallel layout (an explicit all-to-all under EP-2D rules;
    # identical to stage 1 under the default rules, i.e. a no-op).
    buf = with_logical_constraint(buf, "moe_group", "act_expert", "moe_cap", "act_embed")
    buf = with_logical_constraint(buf, "moe_group2", "act_expert2", "moe_cap", "act_embed")

    # --- expert computation (SwiGLU) ---
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
    u = jnp.einsum("becd,edf->becf", buf, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = with_logical_constraint(h, "moe_group2", "act_expert2", "moe_cap", "act_mlp")
    y = jnp.einsum("becf,efd->becd", h, params["w_down"])
    y = with_logical_constraint(y, "moe_group2", "act_expert2", "moe_cap", "act_embed")
    # move results back to the group-sharded layout before the gather
    y = with_logical_constraint(y, "moe_group", "act_expert", "moe_cap", "act_embed")

    # --- gather back + combine with router weights (vmapped, see above) ---
    y_flat = y.reshape(b, ne * cap, d)
    dstc = jnp.minimum(dst, ne * cap - 1)
    gathered = jax.vmap(lambda yg, dg: yg[dg])(y_flat, dstc)
    gathered = jnp.where(keep[..., None], gathered, 0)
    combined = (gathered * wr[..., None]).reshape(b, s, k, d).sum(axis=2)
    combined = combined.reshape(b0, s0, d)
    x = x.reshape(b0, s0, d)

    if e.num_shared_experts:
        sg = jnp.einsum("bsd,df->bsf", x, params["shared_gate"])
        su = jnp.einsum("bsd,df->bsf", x, params["shared_up"])
        sh = jax.nn.silu(sg.astype(jnp.float32)).astype(x.dtype) * su
        combined = combined + jnp.einsum("bsf,fd->bsd", sh, params["shared_down"])

    return combined, aux


def router_load(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Per-expert token counts (for aux-free bias updates / telemetry)."""
    e = cfg.moe
    _, idx, _ = _route(params, x, e)
    return jnp.bincount(idx.reshape(-1), length=e.num_experts)
