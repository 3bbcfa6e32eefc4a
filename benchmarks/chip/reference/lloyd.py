"""Plain Lloyd's KMeans: the float64 reference, and its control in the
precision below the configuration's (float32 matmuls at ``high``, three
bf16 passes, where the configuration states ``highest``).

It imports nothing of the program.  The start is the one the program's
``kmeans(seed=...)`` documents: ``numpy.random.default_rng(seed)
.normal(size=(k, d))`` in float32.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def initial_centroids(seed: int, k: int, d: int) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(k, d)).astype(np.float32)


def lloyd_f64(points: np.ndarray, centroids: np.ndarray,
              iters: int) -> Tuple[np.ndarray, List[float]]:
    """`iters` Lloyd iterations in float64: each one's SSE (before its
    update) and the final centroids.  An empty cluster keeps its centroid."""
    c = centroids.astype(np.float64)
    x = points.astype(np.float64)
    k = c.shape[0]
    x2 = np.sum(x * x, axis=1, keepdims=True)
    sse = []
    for _ in range(iters):
        d2 = x2 - 2.0 * (x @ c.T) + np.sum(c * c, axis=1)[None, :]
        idx = np.argmin(d2, axis=1)
        sse.append(float(d2[np.arange(len(x)), idx].sum()))
        counts = np.bincount(idx, minlength=k)
        sums = np.stack([np.bincount(idx, weights=x[:, j], minlength=k)
                         for j in range(x.shape[1])], axis=1)
        c = c.copy()
        nonempty = counts > 0
        c[nonempty] = sums[nonempty] / counts[nonempty, None]
    return c, sse


def lloyd_high(points: np.ndarray, centroids: np.ndarray,
               iters: int) -> Tuple[np.ndarray, List[float]]:
    """The control: Lloyd's in float32 with every matmul at ``high``
    precision, written out as its three bf16 passes (hi*hi + hi*lo +
    lo*hi) so that it means the same on every platform."""
    import jax
    import jax.numpy as jnp

    def split(a):
        hi = a.astype(jnp.bfloat16)
        lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    def dot3(a, b):
        (ah, al), (bh, bl) = split(a), split(b)
        f = lambda u, v: jnp.dot(u, v, preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.HIGHEST)
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    @jax.jit
    def step(x, c):
        d2 = (jnp.sum(x * x, axis=1, keepdims=True) - 2.0 * dot3(x, c.T)
              + jnp.sum(c * c, axis=1)[None, :])
        idx = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(idx, c.shape[0], dtype=jnp.float32)
        sums = dot3(one_hot.T, x)
        counts = one_hot.sum(axis=0)
        sse = jnp.sum(jnp.take_along_axis(d2, idx[:, None], axis=1))
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(counts, 1.0)[:, None], c)
        return new, sse

    x = jnp.asarray(points, jnp.float32)
    c = jnp.asarray(centroids, jnp.float32)
    sse = []
    for _ in range(iters):
        c, s = step(x, c)
        sse.append(float(s))
    return np.asarray(c), sse


def readings(results: Sequence[Tuple[np.ndarray, Sequence[float]]],
             ref_centroids: np.ndarray,
             ref_sse: Sequence[float]) -> Dict[str, float]:
    """The numbers compared, worst over every result: the SSE of each
    iteration relative to the reference's, and the largest difference of
    a final centroid coordinate."""
    sse_rel, cen = 0.0, 0.0
    for centroids, sse in results:
        if len(sse) != len(ref_sse):
            return {"sse_rel": float("inf"), "centroid_abs": float("inf")}
        sse_rel = max(sse_rel, max(abs(a - b) / abs(b)
                                   for a, b in zip(sse, ref_sse)))
        cen = max(cen, float(np.max(np.abs(
            np.asarray(centroids, np.float64) - ref_centroids))))
    return {"sse_rel": float(sse_rel), "centroid_abs": cen}
