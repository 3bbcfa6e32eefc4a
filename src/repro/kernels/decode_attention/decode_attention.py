"""Pallas TPU kernel: single-token decode attention over a positional KV
cache (flash-decoding adapted to TPU).

GPU flash-decoding splits the KV length across SMs and combines partials;
the TPU adaptation streams KV blocks through a *sequential* grid dimension
with the online-softmax state (m, l, acc) resident in VMEM scratch — the
(1, BK) score tile never touches HBM, so per step the kernel reads exactly
cache + q once: the serving roofline floor. Validity comes from the cache's
stored-position array (slot semantics identical to models/attention.py:
pos >= 0, pos <= current, and optionally within the sliding window).

Grid: (B * Nkv * G, kv_blocks), kv sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(pos_ref, q_ref, k_ref, v_ref, cpos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: int, bk: int,
            nk_blocks: int, g: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (1, H)
    k = k_ref[0].astype(jnp.float32)                  # (BK, H)
    v = v_ref[0].astype(jnp.float32)
    cpos = cpos_ref[0]                                # (1, BK)
    cur = pos_ref[bh]                                 # SMEM scalar
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (1,BK)
    rel = cur - cpos
    valid = (cpos >= 0) & (rel >= 0)
    if window:
        valid &= rel < window
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_pos: jax.Array, positions: jax.Array, *,
                     window: int = 0, block_k: int = 512,
                     interpret: bool = True) -> jax.Array:
    """q (B,Nq,H); k/v_cache (B,Sc,Nkv,H); cache_pos (B,Sc); positions (B,)."""
    b, nq, h = q.shape
    sc, nkv = k_cache.shape[1], k_cache.shape[2]
    g = nq // nkv
    bk = min(block_k, sc)
    assert sc % bk == 0, (sc, bk)
    nkb = sc // bk

    qf = q.reshape(b * nkv * g, 1, h)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * nkv, sc, h)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * nkv, sc, h)
    # each row's current position is a scalar-prefetch operand (SMEM): a
    # (1, 1) VMEM block breaks the TPU's (8, 128) block-shape rule.  The
    # slot positions are (rows, 1, Sc) so a (1, 1, BK) block is legal.
    pos_f = jnp.repeat(positions.astype(jnp.int32), nkv * g)
    cpos_f = jnp.repeat(cache_pos, nkv, axis=0).reshape(b * nkv, 1, sc)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=h ** -0.5, window=window, bk=bk,
                          nk_blocks=nkb, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * nkv * g, nkb),
            in_specs=[
                pl.BlockSpec((1, 1, h), lambda bh, ki, pos: (bh, 0, 0)),
                pl.BlockSpec((1, bk, h), lambda bh, ki, pos: (bh // g, ki, 0)),
                pl.BlockSpec((1, bk, h), lambda bh, ki, pos: (bh // g, ki, 0)),
                pl.BlockSpec((1, 1, bk), lambda bh, ki, pos: (bh // g, 0, ki)),
            ],
            out_specs=pl.BlockSpec((1, 1, h), lambda bh, ki, pos: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, h), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * nkv * g, 1, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos_f, qf, kf, vf, cpos_f)
    return out.reshape(b, nq, h)
