"""Random weights from the seed, made on the device in one jitted call in
the type they are served in.  Both the program under test and the plain
reference are given weights from here, never from each other.

Each leaf is drawn by its name, after Mamba-1's own initialisation:
projections N(0, 1/fan_in), A_log = log(1..n) (S4D-real), dt bias the
inverse softplus of U[1e-3, 1e-1], D and the norms ones, the conv bias
zeros and the embedding N(0, 0.02^2).  A leaf of another name is an
error, so a change of the program's layout shows here.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf(key, name: str, shape, dtype):
    f32 = jnp.float32
    # drawn in the served type: no float32 copy of a large leaf is made
    if name in ("w_in", "w_x", "w_dt", "w_out", "lm_head", "conv_w"):
        return (jax.random.normal(key, shape, dtype)
                * jnp.asarray(1.0 / shape[-2] ** 0.5, dtype))
    if name == "embed":
        return jax.random.normal(key, shape, dtype) * jnp.asarray(0.02, dtype)
    if name in ("norm1", "final_norm", "d_skip"):
        return jnp.ones(shape, f32)
    if name == "conv_b":
        return jnp.zeros(shape, f32)
    if name == "a_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1,
                                                   dtype=f32)), shape)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, f32, 1e-3, 1e-1)
        return u + jnp.log(-jnp.expm1(-u))
    raise KeyError(f"no initialisation for a weight named {name!r}")


def make(abstract, seed: int, device=None):
    """A tree like `abstract` (ShapeDtypeStructs) filled from `seed`."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [jax.tree_util.keystr(p[-1:]).strip("[]'\".") for p, _ in paths]

    def build(key):
        leaves = []
        for (path, spec), name in zip(paths, names):
            k = jax.random.fold_in(key, zlib.crc32(
                jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
            leaves.append(_leaf(k, name, spec.shape, spec.dtype)
                          .astype(spec.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    out = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out)(jax.random.key(seed))
