"""Pallas TPU kernel: Mamba-1 selective scan, chunked recurrence.

TPU adaptation: the CUDA kernel's warp-parallel scan has no direct analogue;
instead each grid step keeps a (N, Dblk) state in VMEM scratch and walks its
chunk of time sequentially with VPU elementwise ops.  The state sits with
Dblk on the 128 lanes and N=16 on the sublanes, so it fills whole (8, 128)
vregs: a time step's dt and x are (1, Dblk) rows broadcast over sublanes,
its B and C are (N, 1) columns broadcast over lanes, and y_t is a sublane
sum.  B and C arrive in (S/16, N, 16) groups so that one group's columns
come from one tile picked by a leading index.  The chunk axis is a
sequential grid dimension: the state never round-trips to HBM between
chunks.

Grid: (batch, di_blocks, chunks) with chunks innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# timesteps per load: a packed bf16 (16, 128) tile holds 16 rows, and the
# compiler refuses a row index it cannot prove tile-aligned
ROWS = 16


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref,
            hout_ref, h_scr, *, chunk: int, steps: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    a = a_ref[...]                                       # (N, Dblk) fp32
    d_skip = d_ref[...]                                  # (1, Dblk) fp32

    def group(gi, h):
        # one aligned (ROWS, Dblk) slab per operand, then static row picks
        ts = pl.ds(pl.multiple_of(gi * ROWS, ROWS), ROWS)
        xs = x_ref[0, ts].astype(jnp.float32)            # (ROWS, Dblk)
        dts = dt_ref[0, ts].astype(jnp.float32)
        dtx = dts * xs
        bs = b_ref[0, gi].astype(jnp.float32)            # (N, ROWS)
        cs = c_ref[0, gi].astype(jnp.float32)
        ys = []
        for j in range(ROWS):
            da = jnp.exp(dts[j:j + 1] * a)               # (N, Dblk)
            h = da * h + dtx[j:j + 1] * bs[:, j:j + 1]
            ys.append(jnp.sum(h * cs[:, j:j + 1], axis=0, keepdims=True))
        y = jnp.concatenate(ys, axis=0) + xs * d_skip
        y_ref[0, ts] = y.astype(y_ref.dtype)
        return h

    # the last chunk may hold fewer than `chunk` steps
    n_groups = jnp.minimum(chunk, steps - ci * chunk) // ROWS
    h_scr[...] = jax.lax.fori_loop(0, n_groups, group, h_scr[...])

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hout_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
                   b_ssm: jax.Array, c_ssm: jax.Array, d_skip: jax.Array,
                   h0: jax.Array | None = None, block_d: int = 512,
                   chunk: int = 256, interpret: bool = False):
    """x, dt (B,S,Di); a (Di,N); b_ssm,c_ssm (B,S,N); d_skip (Di,);
    h0 (B,Di,N) or None for zeros.  Returns (y (B,S,Di) in x's dtype,
    h_end (B,Di,N) fp32), y including the skip term x * d_skip.

    Any S: time is padded to a multiple of ROWS with dt = 0, whose steps
    leave the state exactly as it was (exp(0) = 1, no input), and y is
    sliced back.  block_d must divide Di; chunk is a multiple of ROWS."""
    bsz, s, di = x.shape
    n = a.shape[-1]
    bd = min(block_d, di)
    assert di % bd == 0 and chunk % ROWS == 0, (di, bd, chunk)
    pad = (-s) % ROWS
    if pad:
        x, dt, b_ssm, c_ssm = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                               for t in (x, dt, b_ssm, c_ssm))
    steps = s + pad
    ck = min(chunk, steps)
    n_chunks = pl.cdiv(steps, ck)
    if h0 is None:
        h0 = jnp.zeros((bsz, di, n), jnp.float32)

    def groups(t):  # (B, S, N) -> (B, S/ROWS, N, ROWS)
        return t.reshape(bsz, steps // ROWS, ROWS, n).transpose(0, 1, 3, 2)

    y, h_end = pl.pallas_call(
        functools.partial(_kernel, chunk=ck, steps=steps, n_chunks=n_chunks),
        grid=(bsz, di // bd, n_chunks),
        in_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((n, bd), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, ck // ROWS, n, ROWS), lambda b, d, c: (b, c, 0, 0)),
            pl.BlockSpec((1, ck // ROWS, n, ROWS), lambda b, d, c: (b, c, 0, 0)),
            pl.BlockSpec((1, bd), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, n, bd), lambda b, d, c: (b, 0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, ck, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, n, bd), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, steps, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, n, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
    )(x, dt, a.T.astype(jnp.float32), groups(b_ssm), groups(c_ssm),
      d_skip.reshape(1, di).astype(jnp.float32),
      h0.transpose(0, 2, 1).astype(jnp.float32))
    return y[:, :s], h_end.transpose(0, 2, 1)
