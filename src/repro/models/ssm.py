"""Mamba-1 selective SSM block: chunked parallel scan (train/prefill) and
single-token recurrence (decode).

The train path splits the sequence into chunks; within a chunk the recurrence
h_t = exp(dt_t*A) h_{t-1} + dt_t*B_t x_t runs as a Blelloch associative scan
(parallel, differentiable), and chunk boundaries carry h with an outer
jax.lax.scan — memory stays O(chunk * d_inner * state) instead of
O(seq * d_inner * state).

A prefill on one TPU (the state is returned, nothing is differentiated)
runs the Pallas kernel (repro.kernels.selective_scan) instead: it walks time
sequentially with the state in VMEM, where the associative scan would
materialise (B, chunk, d_inner, state) operands and rewrite them at each of
its levels.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.selective_scan.ops import selective_scan_op
from repro.models.common import ParamSpec, rms_norm
from repro.parallel.sharding import current_context, with_logical_constraint


def ssm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    s = cfg.ssm
    di = s.expand * d
    dt = s.resolved_dt_rank(d)
    n = s.state_dim
    specs = {
        "w_in": ParamSpec((d, 2 * di), ("embed", "ssm_inner"), "scaled"),
        "conv_w": ParamSpec((s.conv_kernel, di), ("conv_k", "ssm_inner"), "scaled"),
        "conv_b": ParamSpec((di,), ("ssm_inner",), "zeros"),
        "w_x": ParamSpec((di, dt + 2 * n), ("ssm_inner", "dt_rank"), "scaled"),
        "w_dt": ParamSpec((dt, di), ("dt_rank", "ssm_inner"), "scaled"),
        "dt_bias": ParamSpec((di,), ("ssm_inner",), "mamba_dt", dtype=jnp.float32),
        "a_log": ParamSpec((di, n), ("ssm_inner", "ssm_state"), "mamba_a",
                           dtype=jnp.float32),
        "d_skip": ParamSpec((di,), ("ssm_inner",), "ones", dtype=jnp.float32),
        "w_out": ParamSpec((di, d), ("ssm_inner", "embed"), "scaled"),
    }
    if s.inner_norms:
        specs["dt_norm"] = ParamSpec((dt,), ("dt_rank",), "ones")
        specs["b_norm"] = ParamSpec((n,), ("ssm_state",), "ones")
        specs["c_norm"] = ParamSpec((n,), ("ssm_state",), "ones")
    return specs


def _selection(params, xc: jax.Array, cfg: ModelConfig):
    """x_proj split into dt (after dt_proj and softplus, float32), B and C,
    with Jamba's RMSNorms on dt, B and C where the config has them."""
    s_cfg = cfg.ssm
    dtr = s_cfg.resolved_dt_rank(cfg.d_model)
    n = s_cfg.state_dim
    proj = jnp.einsum("bsd,de->bse", xc, params["w_x"])
    dt_low, b_ssm, c_ssm = jnp.split(proj, [dtr, dtr + n], axis=-1)
    if s_cfg.inner_norms:
        dt_low = rms_norm(dt_low, params["dt_norm"], cfg.norm_eps)
        b_ssm = rms_norm(b_ssm, params["b_norm"], cfg.norm_eps)
        c_ssm = rms_norm(c_ssm, params["c_norm"], cfg.norm_eps)
    dt = jnp.einsum("bsr,rd->bsd", dt_low, params["w_dt"]).astype(jnp.float32)
    return jax.nn.softplus(dt + params["dt_bias"]), b_ssm, c_ssm


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None):
    """x: (B,S,di); w: (k,di) depthwise. state: (B,k-1,di) carried history."""
    k = w.shape[0]
    if state is None:
        hist = jnp.zeros((x.shape[0], k - 1, x.shape[-1]), x.dtype)
    else:
        hist = state.astype(x.dtype)
    xp = jnp.concatenate([hist, x], axis=1)                # (B, S+k-1, di)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k)) + b
    new_state = xp[:, -(k - 1):] if k > 1 else hist
    return out, new_state


def _chunk_scan(da: jax.Array, bx: jax.Array, h0: jax.Array):
    """Associative scan of h_t = da_t * h_{t-1} + bx_t within one chunk.

    da, bx: (B, c, di, n) fp32; h0: (B, di, n). Returns (ys_states, h_end).
    """
    def combine(a, b):
        (a1, b1), (a2, b2) = a, b
        return a1 * a2, a2 * b1 + b2

    # fold the incoming state into the first step
    bx = bx.at[:, 0].add(da[:, 0] * h0)
    decay, states = jax.lax.associative_scan(combine, (da, bx), axis=1)
    return states, states[:, -1]


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_ssm: jax.Array,
                   c_ssm: jax.Array, d_skip: jax.Array,
                   h0: jax.Array | None = None, chunk: int = 256,
                   scan_dtype=jnp.float32):
    """x, dt: (B,S,di); a: (di,n); b_ssm, c_ssm: (B,S,n). Returns y, h_end.

    scan_dtype: dtype of the associative-scan operands (decay/state). bf16
    halves the dominant HBM traffic of SSM training at ~1e-2 relative state
    drift over a 256-step chunk (chunk boundaries re-enter in fp32).
    """
    bsz, s, di = x.shape
    n = a.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_ssm = jnp.pad(b_ssm, ((0, 0), (0, pad), (0, 0)))
        c_ssm = jnp.pad(c_ssm, ((0, 0), (0, pad), (0, 0)))
    nchunk = x.shape[1] // chunk
    if h0 is None:
        h0 = jnp.zeros((bsz, di, n), jnp.float32)

    def chunk_body(h, xs):
        xc, dtc, bc, cc = xs                               # (B,c,di) / (B,c,n)
        da = jnp.exp(dtc[..., None] * a[None, None])       # (B,c,di,n)
        bx = (dtc * xc)[..., None] * bc[:, :, None, :]     # (B,c,di,n)
        states, h_end = _chunk_scan(da.astype(scan_dtype),
                                    bx.astype(scan_dtype),
                                    h.astype(scan_dtype))
        y = jnp.einsum("bcdn,bcn->bcd", states, cc.astype(scan_dtype))
        return h_end.astype(jnp.float32), y.astype(x.dtype)

    split = lambda t: t.reshape(bsz, nchunk, chunk, -1).transpose(1, 0, 2, 3)
    xs = (split(x), split(dt.astype(jnp.float32)),
          split(b_ssm.astype(jnp.float32)), split(c_ssm.astype(jnp.float32)))
    h_end, ys = jax.lax.scan(jax.checkpoint(chunk_body), h0, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, nchunk * chunk, di)[:, :s]
    # keep the residual path in the activation dtype: an f32 hop here makes
    # every backward cotangent (and the scan's saved-input stash) f32 —
    # observed as a 2x HBM-traffic + stash blowup on falcon-mamba train
    return y + x[:, :s] * d_skip.astype(x.dtype), h_end


def _kernel_scan() -> bool:
    """The Pallas scan runs on a TPU, unsharded: under a mesh of more than
    one device d_inner may be split, and the kernel is not partitioned."""
    ctx = current_context()
    one_device = ctx is None or ctx.mesh is None or ctx.mesh.size == 1
    return jax.default_backend() == "tpu" and one_device


def mamba_forward(params, x: jax.Array, cfg: ModelConfig,
                  state: Dict[str, jax.Array] | None = None,
                  return_state: bool = False):
    """Full-sequence mamba block. x: (B,S,d). Optionally carries/returns state
    {"conv": (B,k-1,di), "ssm": (B,di,n)} for prefill->decode handoff."""
    s_cfg = cfg.ssm
    xz = jnp.einsum("bsd,de->bse", x, params["w_in"])
    xz = with_logical_constraint(xz, "batch", "seq", "act_ssm_inner")
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_state = state["conv"] if state is not None else None
    xc, new_conv = _causal_conv(xi, params["conv_w"], params["conv_b"], conv_state)
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)

    dt, b_ssm, c_ssm = _selection(params, xc, cfg)
    a = -jnp.exp(params["a_log"])

    h0 = state["ssm"] if state is not None else None
    if return_state and _kernel_scan():
        y, h_end = selective_scan_op(xc, dt, a, b_ssm, c_ssm,
                                     params["d_skip"], h0)
    else:
        y, h_end = selective_scan(xc, dt, a, b_ssm, c_ssm, params["d_skip"],
                                  h0=h0, scan_dtype=jnp.dtype(s_cfg.scan_dtype))
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsd,de->bse", y, params["w_out"])
    if return_state:
        return out, {"conv": new_conv, "ssm": h_end}
    return out


def init_ssm_state_spec(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    return {
        "conv": ((batch, s.conv_kernel - 1, di), ("batch", None, "act_ssm_inner")),
        "ssm": ((batch, di, s.state_dim), ("batch", "act_ssm_inner", "ssm_state")),
    }


def mamba_decode(params, x: jax.Array, state: Dict[str, jax.Array],
                 cfg: ModelConfig):
    """Single-token recurrence. x: (B,1,d)."""
    xz = jnp.einsum("bsd,de->bse", x, params["w_in"])
    xi, z = jnp.split(xz, 2, axis=-1)                      # (B,1,di)
    # conv over (history ++ new)
    k = params["conv_w"].shape[0]
    hist = state["conv"].astype(x.dtype)                   # (B,k-1,di)
    window = jnp.concatenate([hist, xi], axis=1)           # (B,k,di)
    xc = (window * params["conv_w"][None]).sum(axis=1, keepdims=True) + params["conv_b"]
    xc = jax.nn.silu(xc.astype(jnp.float32)).astype(x.dtype)
    new_conv = window[:, 1:]

    dt, b_ssm, c_ssm = _selection(params, xc, cfg)
    dt = dt[:, 0]                                          # (B,di)
    a = -jnp.exp(params["a_log"])

    h = state["ssm"]                                       # (B,di,n)
    da = jnp.exp(dt[..., None] * a[None])
    bx = (dt * xc[:, 0].astype(jnp.float32))[..., None] * b_ssm[:, 0, None, :].astype(jnp.float32)
    h_new = da * h + bx
    y = jnp.einsum("bdn,bn->bd", h_new, c_ssm[:, 0].astype(jnp.float32))
    y = (y + xc[:, 0].astype(jnp.float32) * params["d_skip"]).astype(x.dtype)[:, None]
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsd,de->bse", y, params["w_out"])
    return out, {"conv": new_conv, "ssm": h_new}
