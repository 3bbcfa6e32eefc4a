"""Operations and bytes the algorithms need, counted from shapes.

The counts are of the work the algorithm asks for, not of what one
implementation does, so they stay the same whatever runs the step (a
Pallas kernel included).  A multiply-add counts as two operations.

Mamba-1 sizes use the keys of the configuration files (Hugging Face's
names): hidden_size d, intermediate_size e (d_inner), state_size n,
conv_kernel k, time_step_rank r, vocab_size V, num_hidden_layers L.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

BF16 = 2
F32 = 4


def least_time(flops: float, nbytes: float,
               peaks: Dict[str, Any]) -> Tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    t_ops = flops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "operations") if t_ops >= t_mem else (t_mem, "bytes")


# -- KMeans ------------------------------------------------------------------
def kmeans_assign(n: int, k: int, d: int) -> Tuple[float, float]:
    """One Lloyd assignment over n points: the n*k squared distances
    (2*d operations each), the argmin over k, and the per-cluster sums of
    the points (d adds each) and counts; it reads the float32 points and
    centroids once and writes the (k, d) sums and k counts."""
    flops = 2.0 * n * k * d + n * k + n * d + n
    nbytes = F32 * (n * d + k * d + k * d + k)
    return flops, nbytes


# -- Mamba-1 -----------------------------------------------------------------
def _sizes(c: Dict[str, Any]):
    return (c["hidden_size"], c["intermediate_size"], c["state_size"],
            c["conv_kernel"], c["time_step_rank"], c["vocab_size"],
            c["num_hidden_layers"])


def mamba_layer_flops(c: Dict[str, Any]) -> float:
    """One layer, one token: RMSNorm, in_proj (d -> 2e), the depthwise
    causal conv (k taps), x_proj (e -> r + 2n), dt_proj (r -> e), the
    selective scan (per (e, n): exp(dt*A), dt*B*x, h*dA + dBx, h*C), the
    skip and the SiLU gate, out_proj (e -> d) and the residual add."""
    d, e, n, k, r, _, _ = _sizes(c)
    norm = 4 * d
    proj = 2 * d * 2 * e + 2 * e * (r + 2 * n) + 2 * r * e + 2 * e * d
    conv = 2 * k * e + e
    scan = e * n * 7 + e * 2
    gate = e * 4 + e * 2
    return float(norm + proj + conv + scan + gate + d)


def mamba_head_flops(c: Dict[str, Any]) -> float:
    d, _, _, _, _, v, _ = _sizes(c)
    return float(4 * d + 2 * d * v)


def mamba_token_flops(c: Dict[str, Any], with_head: bool) -> float:
    """One token through every layer, and the head where it is read."""
    return (c["num_hidden_layers"] * mamba_layer_flops(c)
            + (mamba_head_flops(c) if with_head else 0.0))


def mamba_weight_bytes(c: Dict[str, Any]) -> float:
    """Weights a decode step reads: every layer's matrices in bf16, its
    A, D and dt bias in float32, the norms and the head; the embedding
    table is gathered, not read (see mamba_decode_step)."""
    d, e, n, k, r, v, nl = _sizes(c)
    layer = (BF16 * (d * 2 * e + k * e + e + e * (r + 2 * n) + r * e
                     + e * d + d)
             + F32 * (e * n + e + e))
    return float(nl * layer + BF16 * (d + d * v))


def mamba_decode_step(c: Dict[str, Any], batch: int) -> Tuple[float, float]:
    """One decode step for `batch` rows: every row's token through every
    layer and the head.  Bytes: the weights once, the embedding rows, the
    float32 SSM state and the conv window read and written, and the bf16
    logits written."""
    d, e, n, k, _, v, nl = _sizes(c)
    flops = batch * mamba_token_flops(c, with_head=True)
    state = nl * batch * (F32 * e * n + BF16 * (k - 1) * e)
    nbytes = (mamba_weight_bytes(c) + BF16 * batch * d + 2 * state
              + BF16 * batch * v)
    return flops, nbytes


def mamba_prefill(c: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """A prefill of one prompt of `tokens`: every token through every
    layer, the head at the last position only.  Bytes: the weights once,
    the prompt's embeddings, the state written."""
    d, e, n, k, _, v, nl = _sizes(c)
    flops = tokens * mamba_token_flops(c, with_head=False) \
        + mamba_head_flops(c)
    nbytes = (mamba_weight_bytes(c) + BF16 * tokens * d
              + nl * (F32 * e * n + BF16 * (k - 1) * e) + BF16 * v)
    return flops, nbytes


def mamba_request_flops(c: Dict[str, Any], prompt_len: int,
                        out_len: int) -> float:
    """A served request: its prompt's prefill (which yields the first
    token) and one decode step of its row for each further token."""
    return (mamba_prefill(c, prompt_len)[0]
            + (out_len - 1) * mamba_token_flops(c, with_head=True))
