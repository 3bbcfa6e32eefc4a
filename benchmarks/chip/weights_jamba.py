"""Random Jamba weights from the seed, made on the device leaf by leaf in
the type they are served in.  Both the program under test and the plain
reference (reference/jamba.py) are given weights from here.

Mamba leaves, the embedding, the head and the final norm are drawn by
weights._leaf (Mamba-1's own initialisation).  The rest: attention
projections, the router (all of its outputs independent), the dense MLP's
and each expert's matrices N(0, 1/fan_in), and the norms (pre-FFN, and
dt/B/C inside the mixer) ones.

The router is then balanced, given the configuration: the residual stream
of a random stack has a common direction (its mean over tokens, from the
Mamba mixers' gated outputs: a sixth of the stream's norm at the first
expert layer, half at the last), and a router drawn at random scores that
mean higher for some experts than for others, so that a few experts and
one half of the 16 took most tokens, a different half for each seed
(PERF.md).
So each expert layer's router, in the order of the layers, loses its
component along that layer's mean input, measured by the reference's
forward over CALIBRATION tokens drawn from the seed: the mean then scores
every expert alike, as a load-balancing loss trains a router to, and
each token's choice rests on what it does not share with the others.

Each leaf is its own small program, and a stacked expert leaf is filled
one expert at a time in place, so no whole-stack or float32 temporary is
ever made: at 14.78 GB of weights a v5e has about 1.7 GB beside them.
A leaf of another name is an error, so a change of the program's layout
shows here.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

import harness

weights = harness.sibling("weights")
jamba = harness.sibling("reference/jamba")

ONES = ("norm2", "dt_norm", "b_norm", "c_norm")
FAN_IN = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "router": 1}    # leading
FFN = ("w_gate", "w_up", "w_down")
CALIBRATION = 1024      # tokens whose mean input each router is balanced on


def _draw(key, name: str, shape, dtype):
    if name in ONES:
        return jnp.ones(shape, dtype)
    if name in FAN_IN:
        fan_in = 1
        for n in shape[:FAN_IN[name]]:
            fan_in *= n
        return (jax.random.normal(key, shape, dtype)
                * jnp.asarray(fan_in ** -0.5, dtype))
    if name in FFN:         # (d, f) or (f, d): fan-in is the first
        return (jax.random.normal(key, shape, dtype)
                * jnp.asarray(shape[-2] ** -0.5, dtype))
    return weights._leaf(key, name, shape, dtype).astype(dtype)


@functools.lru_cache(maxsize=None)
def _leaf_program(name: str, shape, dtype, sharding):
    return jax.jit(functools.partial(_draw, name=name, shape=shape,
                                     dtype=dtype), out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def _expert_program(name: str, shape, dtype, sharding):
    """Draws expert j of a stacked (experts, ...) leaf into its slot."""
    def put(stack, key, j):
        one = _draw(key, name, shape[1:], dtype)
        return jax.lax.dynamic_update_index_in_dim(stack, one, j, 0)
    return jax.jit(put, donate_argnums=0, out_shardings=sharding)


@functools.partial(jax.jit, static_argnames=("eps",))
def _without_mean(router, norm2, x, *, eps: float):
    """`router` less its component along the mean of its input, the
    layer's pre-FFN norm of x (T, d): that mean then scores 0 for every
    expert."""
    mean = jamba._rms(x, norm2, eps).mean(axis=0)
    u = mean / jnp.linalg.norm(mean)
    return router - jnp.outer(u, u @ router)


def balance(params, c: Dict, seed: int):
    """`params` with each expert layer's router balanced on the mean of
    its input (module docstring), the layers taken in order, since a
    router changes what the layers after it see."""
    eps = float(c["rms_norm_eps"])
    tokens = np.random.default_rng(seed).integers(
        0, c["vocab_size"], CALIBRATION)
    layers = list(params["layers"])
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens),
                     axis=0).astype(jnp.float32)
        for i, lp in enumerate(layers):
            if i % c["attn_layer_period"] == c["attn_layer_offset"]:
                x = jamba.attention(lp, x, eps=eps,
                                    kv_heads=int(c["num_key_value_heads"]),
                                    quant=None)
            else:
                x = jamba.mamba(lp, x, eps=eps, r=int(c["mamba_dt_rank"]),
                                quant=None)
            if i % c["expert_layer_period"] == c["expert_layer_offset"]:
                router = _without_mean(lp["moe"]["router"], lp["norm2"], x,
                                       eps=eps)
                lp = layers[i] = dict(lp, moe=dict(lp["moe"], router=router))
                x = jamba.moe(lp, x, c, None)
            else:
                x = jamba.mlp(lp, x, eps=eps, quant=None)
    return dict(params, layers=tuple(layers))


def make(abstract, seed: int, device=None, config: Optional[Dict] = None):
    """A tree like `abstract` (ShapeDtypeStructs) filled from `seed`; with
    `config` (the configuration file's keys), its routers balanced."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    sharding = (None if device is None
                else jax.sharding.SingleDeviceSharding(device))
    root = jax.random.key(seed)
    leaves = []
    for path, spec in paths:
        name = jax.tree_util.keystr(path[-1:]).strip("[]'\".")
        key = jax.random.fold_in(root, zlib.crc32(
            jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        shape, dtype = tuple(spec.shape), jnp.dtype(spec.dtype)
        if name in FFN and len(shape) == 3:
            put = _expert_program(name, shape, dtype, sharding)
            leaf = jnp.zeros(shape, dtype, device=sharding)
            for j in range(shape[0]):
                leaf = put(leaf, jax.random.fold_in(key, j), j)
        else:
            leaf = _leaf_program(name, shape, dtype, sharding)(key)
        leaves.append(leaf)
    params = jax.tree_util.tree_unflatten(treedef, leaves)
    return params if config is None else balance(params, config, seed)
