"""counts.py against counts made by hand."""
import pytest

from chipbench_tiny import harness

counts = harness.sibling("counts")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# a Mamba-1 small enough to count by hand
TINY = {"hidden_size": 4, "intermediate_size": 8, "state_size": 2,
        "conv_kernel": 3, "time_step_rank": 1, "vocab_size": 10,
        "num_hidden_layers": 2}


def test_kmeans_assign():
    flops, nbytes = counts.kmeans_assign(n=10, k=3, d=2)
    # distances 2*n*k*d, argmin n*k, sums n*d, counts n
    assert flops == 120 + 30 + 20 + 10
    # points n*d, centroids k*d, sums k*d, counts k, float32
    assert nbytes == 4 * (20 + 6 + 6 + 3)


def test_kmeans_paper_i_is_bytes_bound():
    t, bound = counts.least_time(*counts.kmeans_assign(1_000_000, 50, 8),
                                 PEAKS)
    assert bound == "bytes"
    assert t == pytest.approx(32e6 / 819e9, rel=0.01)     # ~39 us


def test_mamba_layer_flops_by_hand():
    d, e, n, k, r = 4, 8, 2, 3, 1
    proj = 2 * d * 2 * e + 2 * e * (r + 2 * n) + 2 * r * e + 2 * e * d
    want = 4 * d + proj + (2 * k * e + e) + (7 * e * n + 2 * e) \
        + (4 * e + 2 * e) + d
    assert counts.mamba_layer_flops(TINY) == want
    assert counts.mamba_head_flops(TINY) == 4 * 4 + 2 * 4 * 10


def test_mamba_decode_step_by_hand():
    d, e, n, k, r, v, nl = 4, 8, 2, 3, 1, 10, 2
    b = 5
    flops, nbytes = counts.mamba_decode_step(TINY, b)
    assert flops == b * (nl * counts.mamba_layer_flops(TINY)
                         + counts.mamba_head_flops(TINY))
    layer_w = 2 * (d * 2 * e + k * e + e + e * (r + 2 * n) + r * e + e * d
                   + d) + 4 * (e * n + 2 * e)
    weights = nl * layer_w + 2 * (d + d * v)
    state = nl * b * (4 * e * n + 2 * (k - 1) * e)
    assert nbytes == weights + 2 * b * d + 2 * state + 2 * b * v


def test_mamba_prefill_and_request():
    f1, _ = counts.mamba_prefill(TINY, 1)
    f7, _ = counts.mamba_prefill(TINY, 7)
    per_tok = 2 * counts.mamba_layer_flops(TINY)
    assert f7 - f1 == 6 * per_tok
    req = counts.mamba_request_flops(TINY, 7, 3)
    assert req == f7 + 2 * (per_tok + counts.mamba_head_flops(TINY))


def test_falcon_mamba_decode_is_bytes_bound():
    import json
    from chipbench_tiny import CHIP
    c = json.loads((CHIP / "configs" / "falcon_mamba_7b_l8.json").read_text())
    t, bound = counts.least_time(*counts.mamba_decode_step(c, 32), PEAKS)
    assert bound == "bytes"
    # ~2.2 GB of bf16 weights and ~0.3 GB of state: about 3 ms
    assert 2.5e-3 < t < 3.5e-3
