"""Pallas TPU kernel: Mamba-1 selective scan, chunked recurrence.

TPU adaptation: the CUDA kernel's warp-parallel scan has no direct analogue;
instead the sequence is chunked so each grid step keeps a (Di_blk, N) state
in VMEM scratch and walks its chunk sequentially with VPU elementwise ops
(the (Di, N) lane layout matches the 8x128 VPU tile; N=16 packs the sublane
dim). The chunk axis is a sequential grid dimension — the state never
round-trips to HBM between chunks, which is the entire point.

Grid: (batch, di_blocks, chunks) with chunks innermost/sequential.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# timesteps per load: a packed bf16 (16, 128) tile holds 16 rows, and the
# compiler refuses a row index it cannot prove tile-aligned
_ROWS = 16


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
            h_scr, *, chunk: int, n_chunks: int, rows: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)                   # (Dblk, N)
    d_skip = d_ref[...].astype(jnp.float32)              # (1, Dblk)

    def group(gi, h):
        # one aligned (rows, Dblk) slab per operand, then static row picks
        ts = pl.ds(pl.multiple_of(gi * rows, rows), rows)
        xs = x_ref[0, ts].astype(jnp.float32)            # (rows, Dblk)
        dts = dt_ref[0, ts].astype(jnp.float32)
        bs = b_ref[0, ts].astype(jnp.float32)            # (rows, N)
        cs = c_ref[0, ts].astype(jnp.float32)
        ys = []
        for j in range(rows):
            xt, dtt, bt, ct = xs[j], dts[j], bs[j], cs[j]
            da = jnp.exp(dtt[:, None] * a)               # (Dblk, N)
            h = da * h + (dtt * xt)[:, None] * bt[None, :]
            ys.append(jnp.sum(h * ct[None, :], axis=1) + xt * d_skip[0])
        y_ref[0, ts] = jnp.stack(ys).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // rows, group, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hout_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
                   b_ssm: jax.Array, c_ssm: jax.Array, d_skip: jax.Array,
                   block_d: int = 512, chunk: int = 256,
                   interpret: bool = True):
    """x, dt (B,S,Di); a (Di,N); b_ssm,c_ssm (B,S,N); d_skip (Di,).
    Returns (y (B,S,Di), h_end (B,Di,N))."""
    bsz, s, di = x.shape
    n = a.shape[-1]
    bd = min(block_d, di)
    ck = min(chunk, s)
    assert di % bd == 0 and s % ck == 0, (di, bd, s, ck)
    grid = (bsz, di // bd, s // ck)
    y, h_end = pl.pallas_call(
        functools.partial(_kernel, chunk=ck, n_chunks=s // ck,
                          rows=math.gcd(ck, _ROWS)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((bd, n), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, ck, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, ck, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, bd), lambda b, d, c: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, bd, n), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, b_ssm, c_ssm, d_skip.reshape(1, di))
    return y, h_end
