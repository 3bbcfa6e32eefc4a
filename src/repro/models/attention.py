"""Attention: GQA (full / sliding-window) and MLA (DeepSeek), train + decode.

Pure-jnp math (query-chunked, fp32 softmax) so every cell lowers for the
dry-run on any backend; the Pallas flash kernel (repro.kernels.flash_attention)
is an opt-in drop-in for the TPU target, validated against this path.

Decode uses a unified cache layout: (B, Sc, nkv, hd) K/V plus a (B, Sc) int32
``pos`` array holding the absolute position stored in each slot (-1 = empty).
A rolling (sliding-window) cache is the same structure with Sc = window and
slot = pos % Sc, so full and SWA caches share one code path. MLA decode caches
the compressed latent (kv_lora_rank + rope_dim per token) and uses the
absorbed-matmul trick, which is the point of MLA's serving efficiency.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.common import ParamSpec, apply_rope, rms_norm
from repro.parallel.sharding import with_logical_constraint

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    return {
        "wq": ParamSpec((d, nq, hd), ("embed", "heads", "head_dim"), "scaled"),
        "wk": ParamSpec((d, nkv, hd), ("embed", "kv_heads", "head_dim"), "scaled"),
        "wv": ParamSpec((d, nkv, hd), ("embed", "kv_heads", "head_dim"), "scaled"),
        "wo": ParamSpec((nq, hd, d), ("heads", "head_dim", "embed"), "scaled"),
    }


def _attend_chunked(q, k, v, *, q_positions, kv_positions, causal: bool,
                    window: int, chunk: int = 1024, kv_valid=None):
    """q: (B,S,nkv,g,hd); k,v: (B,Skv,nkv,hd). fp32 online softmax per q-chunk."""
    b, s, nkv, g, hd = q.shape
    scale = hd ** -0.5
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    nchunk = q.shape[1] // chunk
    qs = q.reshape(b, nchunk, chunk, nkv, g, hd).transpose(1, 0, 2, 3, 4, 5)
    qpos = q_positions.reshape(b, nchunk, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        qc, qp = xs                                     # (B,c,nkv,g,hd), (B,c)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qc, k,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((b, 1, 1, chunk, k.shape[1]), jnp.bool_)
        rel = qp[:, :, None] - kv_positions[:, None, :]  # (B,c,Skv)
        if causal:
            mask &= (rel >= 0)[:, None, None]
        if isinstance(window, jax.Array):
            # traced per-layer window (scanned hybrid stacks); 0 = full attn
            mask &= ((window <= 0) | (rel < window))[:, None, None]
        elif window:
            mask &= (rel < window)[:, None, None]
        if kv_valid is not None:
            mask &= kv_valid[:, None, None, None, :]
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
        return carry, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qs, qpos))
    vd = v.shape[-1]  # may differ from q head_dim (MLA)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s + pad, nkv, g, vd)
    return out[:, :s]


def gqa_forward(params, x, *, cfg: ModelConfig, positions, window: int,
                chunk: int = 1024) -> jax.Array:
    """Full-sequence (train / prefill) GQA, with RoPE unless the config
    turns it off."""
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nq // nkv
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = with_logical_constraint(q, "batch", "seq", "act_heads", "act_head_dim")
    k = with_logical_constraint(k, "batch", "seq", "act_kv_heads", "act_head_dim")
    v = with_logical_constraint(v, "batch", "seq", "act_kv_heads", "act_head_dim")
    qg = q.reshape(q.shape[0], q.shape[1], nkv, g, hd)
    out = _attend_chunked(qg, k, v, q_positions=positions,
                          kv_positions=positions, causal=True, window=window,
                          chunk=chunk)
    out = out.reshape(out.shape[0], out.shape[1], nq, hd)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


def gqa_prefill_kv(params, x, *, cfg: ModelConfig, positions):
    """K/V for cache population during prefill (post-RoPE)."""
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.use_rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def init_gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int,
                        window: int) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    sc = min(max_len, window) if window else max_len
    nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    long = max_len >= 2 ** 18 or batch == 1
    seq_ax = "long_seq" if long else "kv_seq"
    return {
        "k": ((batch, sc, nkv, hd), ("batch", seq_ax, "act_kv_heads", "act_head_dim")),
        "v": ((batch, sc, nkv, hd), ("batch", seq_ax, "act_kv_heads", "act_head_dim")),
        "pos": ((batch, sc), ("batch", seq_ax)),
    }


def gqa_decode(params, x, cache, *, cfg: ModelConfig, positions,
               window: int):
    """One-token decode. x: (B,1,d); positions: (B,) absolute position."""
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nq // nkv
    b = x.shape[0]
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.use_rope:
        pos2 = positions[:, None]
        q = apply_rope(q, pos2, cfg.rope_theta)
        k = apply_rope(k, pos2, cfg.rope_theta)
    sc = cache["k"].shape[1]
    slot = positions % sc
    bidx = jnp.arange(b)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
    pos_cache = cache["pos"].at[bidx, slot].set(positions)

    if cfg.decode_kernel:  # Pallas flash-decoding kernel (TPU target)
        from repro.kernels.decode_attention.ops import decode_attention_op
        out = decode_attention_op(q[:, 0], k_cache, v_cache, pos_cache,
                                  positions, window=window,
                                  impl="auto" if jax.default_backend() == "tpu"
                                  else "interpret")
        out = out[:, None]                               # (B,1,Nq,Hd)
    else:
        scale = hd ** -0.5
        qg = q.reshape(b, 1, nkv, g, hd)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                            preferred_element_type=jnp.float32) * scale
        rel = positions[:, None] - pos_cache             # (B,Sc)
        valid = (pos_cache >= 0) & (rel >= 0)
        if window:
            valid &= rel < window
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v_cache.dtype),
                         v_cache)
        out = out.reshape(b, 1, nq, hd)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, {"k": k_cache, "v": v_cache, "pos": pos_cache}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2/V3)
# ---------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, nq, m = cfg.d_model, cfg.num_heads, cfg.mla
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "mla_rank"), "scaled"),
        "q_norm": ParamSpec((m.q_lora_rank,), ("mla_rank",), "ones"),
        "wq_b": ParamSpec((m.q_lora_rank, nq, qh), ("mla_rank", "heads", "head_dim"), "scaled"),
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                           ("embed", "mla_rank"), "scaled"),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("mla_rank",), "ones"),
        "wk_b": ParamSpec((m.kv_lora_rank, nq, m.qk_nope_head_dim),
                          ("mla_rank", "heads", "head_dim"), "scaled"),
        "wv_b": ParamSpec((m.kv_lora_rank, nq, m.v_head_dim),
                          ("mla_rank", "heads", "head_dim"), "scaled"),
        "wo": ParamSpec((nq, m.v_head_dim, d), ("heads", "head_dim", "embed"), "scaled"),
    }


def _mla_qkv_latent(params, x, *, cfg: ModelConfig, positions):
    """Shared projection path: returns per-head q (nope+rope), latent c_kv,
    shared k_rope (post-RoPE)."""
    m = cfg.mla
    q_lat = jnp.einsum("bsd,dr->bsr", x, params["wq_a"])
    q_lat = rms_norm(q_lat, params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", q_lat, params["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_kv = rms_norm(kv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope[..., 0, :]


def mla_forward(params, x, *, cfg: ModelConfig, positions,
                chunk: int = 1024) -> jax.Array:
    """Train/prefill MLA: expand latent to per-head K/V (standard training path)."""
    m = cfg.mla
    nq = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(params, x, cfg=cfg,
                                                   positions=positions)
    k_nope = jnp.einsum("bsr,rhe->bshe", c_kv, params["wk_b"])
    v = jnp.einsum("bsr,rhe->bshe", c_kv, params["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:3] + (m.qk_rope_head_dim,))], axis=-1)
    q = with_logical_constraint(q, "batch", "seq", "act_heads", "act_head_dim")
    k = with_logical_constraint(k, "batch", "seq", "act_heads", "act_head_dim")
    v = with_logical_constraint(v, "batch", "seq", "act_heads", "act_head_dim")
    qg = q[:, :, :, None, :]                            # g=1 (nkv == nq here)
    out = _attend_chunked(qg, k, v, q_positions=positions,
                          kv_positions=positions, causal=True, window=0,
                          chunk=chunk)
    out = out[..., 0, :]
    # NB: scale uses the full qk head dim
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


def init_mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    m = cfg.mla
    return {
        "c_kv": ((batch, max_len, m.kv_lora_rank), ("batch", "kv_seq", "mla_rank")),
        "k_rope": ((batch, max_len, m.qk_rope_head_dim), ("batch", "kv_seq", None)),
        "pos": ((batch, max_len), ("batch", "kv_seq")),
    }


def mla_decode(params, x, cache, *, cfg: ModelConfig, positions):
    """Absorbed-matmul MLA decode against the compressed latent cache."""
    m = cfg.mla
    nq = cfg.num_heads
    b = x.shape[0]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv_latent(
        params, x, cfg=cfg, positions=positions[:, None])
    slot = positions % cache["c_kv"].shape[1]
    bidx = jnp.arange(b)
    c_cache = cache["c_kv"].at[bidx, slot].set(c_kv_new[:, 0].astype(cache["c_kv"].dtype))
    r_cache = cache["k_rope"].at[bidx, slot].set(k_rope_new[:, 0].astype(cache["k_rope"].dtype))
    pos_cache = cache["pos"].at[bidx, slot].set(positions)
    # absorb: q_lat[b,h,r] = q_nope[b,h,e] @ wk_b[r,h,e]
    q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, params["wk_b"])
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_cache,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshe,bte->bhst", q_rope, r_cache,
                           preferred_element_type=jnp.float32)) * scale
    valid = (pos_cache >= 0) & (pos_cache <= positions[:, None])
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhst,btr->bshr", probs.astype(c_cache.dtype), c_cache)
    out = jnp.einsum("bshr,rhe->bshe", out_lat, params["wv_b"])
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, {"c_kv": c_cache, "k_rope": r_cache, "pos": pos_cache}


# ---------------------------------------------------------------------------
# Bidirectional (encoder) + cross attention, for the enc-dec (whisper) family
# ---------------------------------------------------------------------------

def encoder_attention(params, x, *, cfg: ModelConfig, positions):
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nq // nkv
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    qg = q.reshape(q.shape[0], q.shape[1], nkv, g, hd)
    out = _attend_chunked(qg, k, v, q_positions=positions,
                          kv_positions=positions, causal=False, window=0)
    out = out.reshape(out.shape[0], out.shape[1], nq, hd)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


def cross_attention(params, x, enc_k, enc_v, *, cfg: ModelConfig):
    """x: (B,S,d) decoder side; enc_k/enc_v: (B,T,nkv,hd) precomputed."""
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nq // nkv
    b, s = x.shape[:2]
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    qg = q.reshape(b, s, nkv, g, hd)
    t = enc_k.shape[1]
    qpos = jnp.zeros((b, s), jnp.int32)
    kpos = jnp.zeros((b, t), jnp.int32)
    out = _attend_chunked(qg, enc_k, enc_v, q_positions=qpos, kv_positions=kpos,
                          causal=False, window=0)
    out = out.reshape(b, s, nq, hd)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


def cross_kv(params, enc_out):
    k = jnp.einsum("btd,dhe->bthe", enc_out, params["wk"])
    v = jnp.einsum("btd,dhe->bthe", enc_out, params["wv"])
    return k, v
