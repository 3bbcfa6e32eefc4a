"""KMeans on Pilot-Data Memory — the paper's §4.3 validation workload.

Each iteration is one map_reduce over the points DU:
  map(points_partition, centroids) -> (partial_sums (K,D), counts (K), sse)
  reduce = elementwise add
The centroids update on the driver (paper: 'the centroids vector changes
each iteration'), while the points DU stays wherever its tier keeps it —
file tier re-reads every iteration (paper's file backend), device tier
keeps points in HBM across iterations (paper's Spark backend, the 212x).

The assignment map is the compute hot-spot; kernels/kmeans provides the
Pallas TPU kernel for it (MXU-tiled distance matmul), with the jnp oracle
used everywhere a TPU is absent.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.data import DataUnit
from repro.core.manager import ComputeDataManager
from repro.core.mapreduce import map_reduce
from repro.core.pilot import PilotCompute

# the paper's three scenarios: (points, clusters) with constant points*k
PAPER_SCENARIOS = {
    "i": (1_000_000, 50),
    "ii": (100_000, 500),
    "iii": (10_000, 5_000),
}


def assign_partial(points: jax.Array, centroids: jax.Array):
    """Map phase: nearest-centroid assignment + partial centroid sums.

    points (N,D), centroids (K,D) -> (sums (K,D), counts (K,), sse ()).
    Uses the |x-c|^2 = |x|^2 - 2 x.c + |c|^2 matmul form (MXU-friendly;
    mirrored by the Pallas kernel in repro.kernels.kmeans).  Both matmuls
    run at full float32 precision: the TPU's default single bf16 pass
    leaves x.c off by ~2^-9 |x||c|, which the subtraction turns into a
    per-cluster bias of a few percent of the SSE.
    """
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    x = points.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)            # (N,1)
    c2 = jnp.sum(c * c, axis=1)[None, :]                  # (1,K)
    d2 = x2 - 2.0 * dot(x, c.T) + c2                      # (N,K)
    idx = jnp.argmin(d2, axis=1)
    one_hot = jax.nn.one_hot(idx, c.shape[0], dtype=jnp.float32)
    sums = dot(one_hot.T, x)                              # (K,D)
    counts = one_hot.sum(axis=0)                          # (K,)
    sse = jnp.sum(jnp.take_along_axis(d2, idx[:, None], axis=1))
    return sums, counts, sse


def _reduce(a, b):
    return jax.tree.map(lambda u, v: u + v, a, b)


@dataclasses.dataclass
class KMeansResult:
    centroids: np.ndarray
    sse_history: list
    iter_seconds: list
    total_seconds: float
    tier: str


def kmeans(du: DataUnit, k: int, iters: int = 5,
           manager: Optional[ComputeDataManager] = None,
           pilot: Optional[PilotCompute] = None,
           map_fn: Callable = assign_partial,
           seed: int = 0, prefetch_depth: Optional[int] = None,
           pipeline: bool = True) -> KMeansResult:
    """Lloyd's algorithm over a (possibly tiered) points DataUnit.

    prefetch_depth/pipeline tune the pipelined map_reduce engine (None =
    adaptive depth from measured stage/compute times); use pipeline=False
    for the sequential i+1-prefetch baseline."""
    d = int(np.asarray(du.partition(0)).shape[1])
    rng = np.random.default_rng(seed)
    centroids = rng.normal(size=(k, d)).astype(np.float32)
    sse_hist, iter_secs = [], []
    t_start = time.time()
    for _ in range(iters):
        t0 = time.time()
        # host centroids: each pilot's jitted map moves them to the chips
        # its partitions sit on, not to the default device
        sums, counts, sse = map_reduce(du, map_fn, _reduce, manager=manager,
                                       pilot=pilot, extra_args=(centroids,),
                                       prefetch_depth=prefetch_depth,
                                       pipeline=pipeline)
        sums, counts, sse = map(np.asarray, (sums, counts, sse))
        nonempty = counts > 0
        centroids = centroids.copy()
        centroids[nonempty] = sums[nonempty] / counts[nonempty, None]
        sse_hist.append(float(sse))
        iter_secs.append(time.time() - t0)
    return KMeansResult(centroids=centroids, sse_history=sse_hist,
                        iter_seconds=iter_secs,
                        total_seconds=time.time() - t_start, tier=du.tier)


def make_blobs(n: int, k: int, d: int = 8, seed: int = 0,
               spread: float = 0.15) -> Tuple[np.ndarray, np.ndarray]:
    """Synthetic clustered data (the experiments' input generator)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32)
    labels = rng.integers(0, k, size=n)
    pts = centers[labels] + spread * rng.normal(size=(n, d)).astype(np.float32)
    return pts.astype(np.float32), labels
