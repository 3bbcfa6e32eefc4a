"""Pallas TPU kernel: fused KMeans assignment + partial centroid sums.

The paper's KMeans map phase ("compute the closest centroid for each point")
is the analytics hot-spot (§4.3). TPU adaptation: the pairwise-distance
matrix is computed in its matmul form so the MXU does the heavy lifting,
and the one-hot partial-sum reduction is a second MXU matmul — the whole
map phase is two matmuls + a VPU argmin, fused in VMEM so the (BN, K)
distance block never touches HBM.

Grid: one program per point-block; centroids stay VMEM-resident across the
grid; partial sums/counts/sse accumulate in the revisited output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, sums_ref, counts_ref, sse_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sse_ref[...] = jnp.zeros_like(sse_ref)

    x = x_ref[...].astype(jnp.float32)              # (BN, D)
    c = c_ref[...].astype(jnp.float32)              # (K, D)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    d2 = x2 - 2.0 * jnp.dot(x, c.T, preferred_element_type=jnp.float32) + c2
    # argmin as min + iota compare, all in (BN, K)/(BN, 1) column layout:
    # jnp.argmin's (BN,) result needs a lane->sublane relayout to broadcast
    # back against the columns, which the TPU compiler refuses
    k = c.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    best = jnp.min(d2, axis=1, keepdims=True)       # (BN, 1)
    idx = jnp.min(jnp.where(d2 == best, col, k), axis=1, keepdims=True)
    one_hot = (col == idx).astype(jnp.float32)      # first minimum, as argmin
    sums_ref[...] += jnp.dot(one_hot.T, x, preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(one_hot, axis=0, keepdims=True)
    sse_ref[...] += jnp.sum(best, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(points: jax.Array, centroids: jax.Array,
                  block_n: int = 1024, interpret: bool = True):
    """points (N,D), centroids (K,D) -> (sums (K,D), counts (K,), sse ()).

    N must be a multiple of block_n (ops.py pads). K*D and BN*K blocks must
    fit VMEM: defaults target (K<=4096, D<=512) at fp32.
    """
    n, d = points.shape
    k = centroids.shape[0]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    sums, counts, sse = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids)
    return sums, counts[0], sse[0, 0]
