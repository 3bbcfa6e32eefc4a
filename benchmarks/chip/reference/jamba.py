"""A plain float32 Jamba forward (transformers' models/jamba/
modeling_jamba.py, the slow path), the reference for serving, and its
control.

No cache, kernel or batching: one sequence at a time, one layer at a time,
every matmul at ``highest`` precision.  A layer is attention where
``i % attn_layer_period == attn_layer_offset`` and Mamba-1 elsewhere; its
FFN is the expert layer where ``i % expert_layer_period ==
expert_layer_offset`` and a dense SwiGLU elsewhere.  The Mamba mixer's
selective scan is the plain recurrence
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,   y_t = C_t h_t + D x_t
after RMSNorms on dt, B and C; attention is causal softmax at scale
1/sqrt(head_dim) with no rotary embedding; the router is a float32 softmax
over all router_experts outputs, each token's top num_experts_per_tok
weights kept as they are (not renormalised), and only the held experts'
terms added (this chip's share; the others are another chip's part).
It imports nothing of the program and is given its weights by
weights_jamba.py.

To fit on the chip beside the bf16 weights, attention runs in query
blocks and each expert is upcast alone and run over token blocks.

The control is the same forward with every matmul's operands in float8
(e4m3, one scale per tensor), the step below the bfloat16 that the
configuration states.

Departures from modeling_jamba.py: none.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 512             # query rows (attention) and tokens (experts) a step


def _q(a, quant: Optional[str]):
    if quant is None:
        return a
    s = jnp.max(jnp.abs(a)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, w, quant):
    return jnp.dot(_q(a, quant), _q(w, quant))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _blocks(fn, *xs):
    """fn over row blocks of xs (each (T, ...)), T padded to whole blocks
    with zeros and cut back."""
    t = xs[0].shape[0]
    pad = (-t) % BLOCK

    def split(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, BLOCK) + a.shape[1:])

    out = jax.lax.map(lambda b: fn(*b), tuple(split(a) for a in xs))
    return out.reshape((-1,) + out.shape[2:])[:t]


@functools.partial(jax.jit, static_argnames=("eps", "r", "quant"))
def mamba(lp, x, *, eps: float, r: int, quant: Optional[str]):
    """x + the Mamba-1 mixer of the layer's input norm; x (T, d) float32."""
    s = jax.tree.map(lambda t: t.astype(F32), lp["ssm"])
    t_len = x.shape[0]
    h = _rms(x, lp["norm1"], eps)
    xi, z = jnp.split(_mm(h, s["w_in"], quant), 2, axis=-1)
    k, e = s["conv_w"].shape
    xp = jnp.concatenate([jnp.zeros((k - 1, e), F32), xi])
    xc = jax.nn.silu(sum(xp[i:i + t_len] * s["conv_w"][i] for i in range(k))
                     + s["conv_b"])
    n = s["a_log"].shape[-1]
    dt_low, b, c = jnp.split(_mm(xc, s["w_x"], quant), [r, r + n], axis=-1)
    dt_low = _rms(dt_low, s["dt_norm"], eps)
    b = _rms(b, s["b_norm"], eps)
    c = _rms(c, s["c_norm"], eps)
    dt = jax.nn.softplus(_mm(dt_low, s["w_dt"], quant) + s["dt_bias"])
    a = -jnp.exp(s["a_log"])

    def step(state, inp):
        dt_t, b_t, c_t, x_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t + s["d_skip"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((e, n), F32), (dt, b, c, xc))
    return x + _mm(y * jax.nn.silu(z), s["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "kv_heads", "quant"))
def attention(lp, x, *, eps: float, kv_heads: int, quant: Optional[str]):
    """x + causal GQA of the layer's input norm, no positions encoded."""
    p = jax.tree.map(lambda t: t.astype(F32), lp["attn"])
    t_len, d = x.shape
    nq, hd = p["wq"].shape[1:]
    h = _rms(x, lp["norm1"], eps)
    q = _mm(h, p["wq"].reshape(d, -1), quant).reshape(t_len, nq, hd)
    k = _mm(h, p["wk"].reshape(d, -1), quant).reshape(t_len, kv_heads, hd)
    v = _mm(h, p["wv"].reshape(d, -1), quant).reshape(t_len, kv_heads, hd)
    k = jnp.repeat(k, nq // kv_heads, axis=1)
    v = jnp.repeat(v, nq // kv_heads, axis=1)
    keys = jnp.arange(t_len)

    def block(qb, rows):
        scores = jnp.einsum("qhd,khd->hqk", _q(qb, quant),
                            _q(k, quant)) / np.sqrt(hd)
        scores = jnp.where(keys[None, None, :] <= rows[None, :, None],
                           scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(probs, quant), _q(v, quant))

    out = _blocks(block, q, keys)
    return x + _mm(out.reshape(t_len, nq * hd), p["wo"].reshape(nq * hd, d),
                   quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def mlp(lp, x, *, eps: float, quant: Optional[str]):
    """x + the dense SwiGLU of the layer's pre-FFN norm."""
    f = jax.tree.map(lambda t: t.astype(F32), lp["ffn"])
    h = _rms(x, lp["norm2"], eps)
    return x + _blocks(lambda hb: _swiglu(hb, f["w_gate"], f["w_up"],
                                          f["w_down"], quant), h)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "quant"))
def route(lp, x, *, eps: float, top_k: int, quant: Optional[str]):
    """The pre-FFN norm and each token's kept router weights (T, E): its
    top_k softmax probabilities over every router output, zero elsewhere."""
    h = _rms(x, lp["norm2"], eps)
    probs = jax.nn.softmax(_mm(h, lp["moe"]["router"].astype(F32), quant),
                           axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    rows = jnp.arange(h.shape[0])[:, None]
    return h, jnp.zeros_like(probs).at[rows, idx].set(w)


@functools.partial(jax.jit, static_argnames=("quant",))
def expert(h, m, j, *, quant: Optional[str]):
    """Held expert j's SwiGLU over every token: its bf16 weights taken from
    the layer's stacks and upcast here, one expert's at a time."""
    wg, wu, wd = (m[n][j].astype(F32) for n in ("w_gate", "w_up", "w_down"))
    return _blocks(lambda hb: _swiglu(hb, wg, wu, wd, quant), h)


def moe(lp, x, cfg: Dict, quant: Optional[str]):
    """x + the held experts' terms of the expert layer, one expert at a
    time: expert lo + j's output weighted by each token's kept weight."""
    lo, hi = cfg["experts_held"]
    h, gate = route(lp, x, eps=float(cfg["rms_norm_eps"]),
                    top_k=int(cfg["num_experts_per_tok"]), quant=quant)
    for j in range(hi - lo):
        x = x + gate[:, lo + j, None] * expert(h, lp["moe"], j, quant=quant)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x_rows, final_norm, lm_head, *, eps: float, quant: Optional[str]):
    return _mm(_rms(x_rows, final_norm, eps), lm_head.astype(F32), quant)


def logits_at(params, tokens: np.ndarray, positions: np.ndarray,
              cfg: Dict, bucket: int, out_bucket: int,
              quant: Optional[str] = None) -> jax.Array:
    """Float32 logits (len(positions), V) of `tokens` read at `positions`.
    Sequences are padded at the end to `bucket` (causal: later positions
    change nothing before them) so that one program serves every request."""
    eps = float(cfg["rms_norm_eps"])
    seq = np.zeros(bucket, np.int32)
    seq[:len(tokens)] = tokens
    pos = np.zeros(out_bucket, np.int32)
    pos[:len(positions)] = positions
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(seq), axis=0).astype(F32)
        for i, lp in enumerate(params["layers"]):
            if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
                x = attention(lp, x, eps=eps,
                              kv_heads=int(cfg["num_key_value_heads"]),
                              quant=quant)
            else:
                x = mamba(lp, x, eps=eps, r=int(cfg["mamba_dt_rank"]),
                          quant=quant)
            if i % cfg["expert_layer_period"] == cfg["expert_layer_offset"]:
                x = moe(lp, x, cfg, quant)
            else:
                x = mlp(lp, x, eps=eps, quant=quant)
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
