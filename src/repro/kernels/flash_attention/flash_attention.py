"""Pallas TPU kernel: causal/windowed GQA flash attention (forward).

TPU adaptation of the IO-aware attention insight (FlashAttention): stream KV
blocks through VMEM while the (BQ, BK) score tile lives entirely on-chip;
online-softmax running max/sum and the output accumulator sit in VMEM
scratch, so HBM traffic is O(S*(d + d)) instead of O(S^2). Block shapes are
MXU-aligned (multiples of 128 on the contracting/lane dims).

Grid: (batch*kv_heads*group, q_blocks, kv_blocks), kv innermost and
sequential (scratch carries across it); q/batch dims parallel. GQA is
handled by the index map: program bh covers q head (kv_head, g) and loads
the kv_head's K/V block — no KV duplication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skip: fully-masked blocks contribute nothing
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + bq - 1
    if window:
        run &= (q_start - (k_start + bk - 1)) < window

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # (BQ, H)
        k = k_ref[0].astype(jnp.float32)                 # (BK, H)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        rel = qpos - kpos
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= rel >= 0
        if window:
            mask &= rel < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                              # (BQ, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == nk_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = True) -> jax.Array:
    """q (B,Sq,Nq,H); k,v (B,Skv,Nkv,H) -> (B,Sq,Nq,H). Self-attention."""
    b, sq, nq, h = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    nqb, nkb = sq // bq, skv // bk

    # flatten heads into the leading grid dim: bh = ((b * nkv) + kh) * g + gi
    qf = q.reshape(b, sq, nkv * g, h).transpose(0, 2, 1, 3).reshape(
        b * nkv * g, sq, h)
    kf = k.transpose(0, 2, 1, 3).reshape(b * nkv, skv, h)
    vf = v.transpose(0, 2, 1, 3).reshape(b * nkv, skv, h)

    grid = (b * nkv * g, nqb, nkb)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=h ** -0.5, causal=causal,
                          window=window, bq=bq, bk=bk, nk_blocks=nkb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, h), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, h), lambda bh, qi, ki: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, h), lambda bh, qi, ki: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, h), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * nkv * g, sq, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, nkv * g, sq, h).transpose(0, 2, 1, 3)
