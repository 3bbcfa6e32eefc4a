"""Operations and bytes a Jamba layer pattern needs, counted from shapes
(configs/jamba2_mini_l8.json's keys, Hugging Face's names).

As in counts.py, the counts are of the work the algorithm asks for, and a
multiply-add counts as two operations.  Sizes: hidden_size d,
intermediate_size f (the dense MLP's and each expert's width), d_inner
e = mamba_expand * d, mamba_d_state n, mamba_d_conv k, mamba_dt_rank r,
heads nq and nkv of d / nq, vocab_size V, router_experts E, the
num_experts held here H, num_experts_per_tok K.

An expert layer's work per token is K * H / E experts' SwiGLU: each token
picks K of E experts and this chip computes those it holds, which under
uniform routing is top-2 x 8/16 = one expert per token.  A decode step's
bytes count every held expert's weights: its 32 rows make 64 assignments
over 16 experts, so nearly every held expert is read each step.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

import harness

counts = harness.sibling("counts")
BF16, F32 = counts.BF16, counts.F32
least_time = counts.least_time


def _sizes(c: Dict[str, Any]):
    d = c["hidden_size"]
    return (d, c["intermediate_size"], c["mamba_expand"] * d,
            c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"],
            c["num_attention_heads"], c["num_key_value_heads"],
            d // c["num_attention_heads"], c["vocab_size"])


def kinds(c: Dict[str, Any]):
    """Each layer's (mixer, ffn): HF's ``i % period == offset`` rule."""
    return [("attn" if i % c["attn_layer_period"] == c["attn_layer_offset"]
             else "ssm",
             "moe" if i % c["expert_layer_period"] == c["expert_layer_offset"]
             else "dense")
            for i in range(c["num_hidden_layers"])]


def experts_per_token(c: Dict[str, Any]) -> float:
    return c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]


def mamba_flops(c: Dict[str, Any]) -> float:
    """One token: input norm, in_proj (d -> 2e), conv (k taps), x_proj
    (e -> r + 2n), the dt/B/C norms, dt_proj, the scan (7 per (e, n)),
    skip and gate, out_proj, residual."""
    d, _, e, n, k, r = _sizes(c)[:6]
    return float(4 * d + 2 * d * 2 * e + (2 * k * e + e)
                 + 2 * e * (r + 2 * n) + 4 * (r + 2 * n) + 2 * r * e
                 + (7 * e * n + 2 * e) + 6 * e + 2 * e * d + d)


def attn_flops(c: Dict[str, Any], keys: float) -> float:
    """One query over `keys` cached positions: input norm, q/k/v/o
    projections, scores and the weighted sum (2 * hd each per key and
    head), the softmax (about 5 per key and head), residual."""
    d, nq, nkv, hd = c["hidden_size"], *_sizes(c)[6:9]
    proj = 2 * d * nq * hd + 2 * 2 * d * nkv * hd + 2 * nq * hd * d
    return float(4 * d + proj + nq * keys * (4 * hd + 5) + d)


def dense_flops(c: Dict[str, Any]) -> float:
    """Pre-FFN norm, SwiGLU (three d x f matmuls, the gate), residual."""
    d, f = c["hidden_size"], c["intermediate_size"]
    return float(4 * d + 6 * d * f + 5 * f + d)


def moe_flops(c: Dict[str, Any]) -> float:
    """Pre-FFN norm, router (d -> E) and its softmax, K * H / E experts'
    SwiGLU and their weighted sum, residual."""
    d, f, ne = c["hidden_size"], c["intermediate_size"], c["router_experts"]
    x = experts_per_token(c)
    return float(4 * d + 2 * d * ne + 5 * ne + x * (6 * d * f + 5 * f + 2 * d)
                 + d)


def head_flops(c: Dict[str, Any]) -> float:
    d, v = c["hidden_size"], c["vocab_size"]
    return float(4 * d + 2 * d * v)


def token_flops(c: Dict[str, Any], keys: float, with_head: bool) -> float:
    """One token through every layer, its attention over `keys` positions."""
    total = 0.0
    for mixer, ffn in kinds(c):
        total += attn_flops(c, keys) if mixer == "attn" else mamba_flops(c)
        total += moe_flops(c) if ffn == "moe" else dense_flops(c)
    return total + (head_flops(c) if with_head else 0.0)


def weight_bytes(c: Dict[str, Any]) -> float:
    """Every weight a decode step reads: each layer's matrices and norms in
    bf16 (A, D, dt bias and the router in float32), all held experts, the
    final norm and the head; the embedding table is gathered, not read."""
    d, f, e, n, k, r, nq, nkv, hd, v = _sizes(c)
    mamba = (BF16 * (d * 2 * e + k * e + e + e * (r + 2 * n) + r * e + e * d
                     + r + 2 * n)
             + F32 * (e * n + e + e))
    attn = BF16 * (d * nq * hd + 2 * d * nkv * hd + nq * hd * d)
    dense = BF16 * 3 * d * f
    moe = BF16 * c["num_experts"] * 3 * d * f + F32 * d * c["router_experts"]
    total = 0.0
    for mixer, ffn in kinds(c):
        total += BF16 * 2 * d + (attn if mixer == "attn" else mamba)
        total += moe if ffn == "moe" else dense
    return total + BF16 * (d + d * v)


def state_bytes(c: Dict[str, Any], rows: int) -> float:
    """The Mamba layers' float32 SSM state and bf16 conv window."""
    _, _, e, n, k = _sizes(c)[:5]
    ssm = sum(m == "ssm" for m, _ in kinds(c))
    return float(ssm * rows * (F32 * e * n + BF16 * (k - 1) * e))


def kv_bytes(c: Dict[str, Any], positions: float) -> float:
    """bf16 K and V of every attention layer over `positions` tokens."""
    nkv, hd = _sizes(c)[7:9]
    attn = sum(m == "attn" for m, _ in kinds(c))
    return float(attn * positions * 2 * nkv * hd * BF16)


def decode_step(c: Dict[str, Any], batch: int,
                fill: float) -> Tuple[float, float]:
    """One decode step for `batch` rows whose caches hold `fill` tokens on
    average.  Bytes: the weights once, the rows' embeddings, K/V read at
    the fill and one position written a row, the SSM state read and
    written, the bf16 logits written."""
    d, v = c["hidden_size"], c["vocab_size"]
    flops = batch * token_flops(c, fill + 1, with_head=True)
    nbytes = (weight_bytes(c) + BF16 * batch * d
              + kv_bytes(c, batch * (fill + 1)) + 2 * state_bytes(c, batch)
              + BF16 * batch * v)
    return flops, nbytes


def prefill(c: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """A causal prefill of one prompt: token t attends to t positions, the
    head at the last position only.  Bytes: the weights once, the prompt's
    embeddings, K/V and the state written, the logits."""
    d, v = c["hidden_size"], c["vocab_size"]
    keys = (tokens + 1) / 2.0             # mean over t = 1..tokens
    flops = tokens * token_flops(c, keys, with_head=False) + head_flops(c)
    nbytes = (weight_bytes(c) + BF16 * tokens * d + kv_bytes(c, tokens)
              + state_bytes(c, 1) + BF16 * v)
    return flops, nbytes


def request_flops(c: Dict[str, Any], prompt_len: int, out_len: int) -> float:
    """A served request: its prompt's prefill (which yields the first
    token) and one decode step of its row for each further token, the g-th
    attending to prompt_len + g positions."""
    g = out_len - 1
    keys = prompt_len + (g + 1) / 2.0     # mean over g' = 1..g
    return (prefill(c, prompt_len)[0]
            + g * token_flops(c, keys, with_head=True))


def decode_fill(requests: Iterable[Dict[str, Any]]) -> float:
    """Mean cache fill of the decode steps of `requests` (each with
    ``prompt`` and ``out_len``): step g = 1..out_len-1 of a row finds
    prompt + g - 1 tokens cached and writes one more."""
    tokens = steps = 0.0
    for q in requests:
        p, g = len(q["prompt"]), q["out_len"] - 1
        tokens += g * p + g * (g - 1) / 2.0
        steps += g
    return tokens / steps if steps else 0.0
