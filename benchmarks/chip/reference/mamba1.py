"""A plain float32 Mamba-1 forward (arXiv:2312.00752, Algorithm 2), the
reference for serving, and its control.

No cache, kernel or batching: one sequence at a time, one layer at a
time, the selective scan as the plain recurrence
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,   y_t = C_t h_t + D x_t
over every position, with every matmul at ``highest`` precision.  It
imports nothing of the program and is given its weights by weights.py.

The control is the same forward with every matmul's operands in float8
(e4m3, one scale per tensor), the step below the bfloat16 that the
configuration states.

It follows the repo's Mamba-1 (models/ssm.py) where Falcon-Mamba departs
from it: Falcon-Mamba's RMSNorms on B, C and dt are left out of both.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _q(a, quant: Optional[str]):
    if quant is None:
        return a
    s = jnp.max(jnp.abs(a)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, quant):
    return jnp.dot(_q(a, quant), _q(w, quant))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


@functools.partial(jax.jit, static_argnames=("eps", "r", "quant"))
def layer(lp, x, *, eps: float, r: int, quant: Optional[str]):
    """One residual Mamba-1 block over x (T, d), float32."""
    p = jax.tree.map(lambda t: t.astype(F32), lp)
    s = p["ssm"]
    t_len = x.shape[0]
    h = _rms(x, p["norm1"], eps)
    xi, z = jnp.split(_mm(h, s["w_in"], quant), 2, axis=-1)
    k, e = s["conv_w"].shape
    xp = jnp.concatenate([jnp.zeros((k - 1, e), F32), xi])
    xc = jax.nn.silu(sum(xp[i:i + t_len] * s["conv_w"][i] for i in range(k))
                     + s["conv_b"])
    n = s["a_log"].shape[-1]
    dt_low, b, c = jnp.split(_mm(xc, s["w_x"], quant), [r, r + n], axis=-1)
    dt = jax.nn.softplus(_mm(dt_low, s["w_dt"], quant) + s["dt_bias"])
    a = -jnp.exp(s["a_log"])

    def step(state, inp):
        dt_t, b_t, c_t, x_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t + s["d_skip"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((e, n), F32), (dt, b, c, xc))
    return x + _mm(y * jax.nn.silu(z), s["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x_rows, final_norm, lm_head, *, eps: float, quant: Optional[str]):
    return _mm(_rms(x_rows, final_norm.astype(F32), eps),
               lm_head.astype(F32), quant)


def logits_at(params, tokens: np.ndarray, positions: np.ndarray,
              cfg: Dict, bucket: int, out_bucket: int,
              quant: Optional[str] = None) -> jax.Array:
    """Float32 logits (len(positions), V) of `tokens` read at `positions`.
    Sequences are padded at the end to `bucket` (causal: later positions
    change nothing before them) so that one program serves every request."""
    eps = float(cfg["layer_norm_epsilon"])
    r = int(cfg["time_step_rank"])
    seq = np.zeros(bucket, np.int32)
    seq[:len(tokens)] = tokens
    pos = np.zeros(out_bucket, np.int32)
    pos[:len(positions)] = positions
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(seq), axis=0).astype(F32)
        n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        for i in range(n_layers):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            x = layer(lp, x, eps=eps, r=r, quant=quant)
        out = head(x[jnp.asarray(pos)], params["final_norm"],
                   params["lm_head"], eps=eps, quant=quant)
    return out[:len(positions)]


def served_gaps(params, prompt: np.ndarray, served: Sequence[int],
                cfg: Dict, bucket: int, out_bucket: int,
                control: bool = False) -> Dict[str, float]:
    """For one finished request: the widest gap by which a served token's
    reference logit lies below the reference's best at its position.  With
    `control`, also the widest gap of the token the float8 forward puts
    first at each of those positions."""
    served = np.asarray(served, np.int32)
    tokens = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    positions = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = np.asarray(logits_at(params, tokens, positions, cfg, bucket,
                               out_bucket))
    best = ref.max(axis=-1)
    rows = np.arange(len(served))
    out = {"gap": float(np.max(best - ref[rows, served]))}
    if control:
        ctl = np.asarray(logits_at(params, tokens, positions, cfg, bucket,
                                   out_bucket, quant="float8"))
        out["control_gap"] = float(np.max(best - ref[rows,
                                                     ctl.argmax(axis=-1)]))
    return out
