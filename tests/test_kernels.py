"""Per-kernel validation: interpret-mode pallas_call vs pure-jnp oracle,
with hypothesis sweeps over shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.kmeans.kmeans import kmeans_assign
from repro.kernels.kmeans.ops import kmeans_assign_op
from repro.kernels.kmeans.ref import kmeans_assign_ref
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.selective_scan.selective_scan import selective_scan

SETTINGS = dict(max_examples=8, deadline=None)


# ---------------------------------------------------------------- kmeans ---
@settings(**SETTINGS)
@given(n=st.sampled_from([256, 512, 1000]),
       d=st.sampled_from([4, 8, 32]),
       k=st.sampled_from([5, 16, 64]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_kmeans_kernel_matches_ref(n, d, k, dtype):
    pts = jax.random.normal(jax.random.key(0), (n, d), dtype)
    cen = jax.random.normal(jax.random.key(1), (k, d), dtype)
    s1, c1, e1 = kmeans_assign_op(pts, cen, block_n=128, impl="interpret")
    s2, c2, e2 = kmeans_assign_ref(pts, cen)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=tol,
                               atol=tol * 10)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(float(e1), float(e2), rtol=tol)


def test_kmeans_counts_sum_to_n():
    pts = jax.random.normal(jax.random.key(2), (512, 8), jnp.float32)
    cen = jax.random.normal(jax.random.key(3), (16, 8), jnp.float32)
    _, counts, _ = kmeans_assign(pts, cen, block_n=128, interpret=True)
    assert int(counts.sum()) == 512


# ------------------------------------------------------------ flash attn ---
@settings(**SETTINGS)
@given(sq=st.sampled_from([128, 256, 384]),
       heads=st.sampled_from([(4, 2), (4, 4), (6, 3)]),
       h=st.sampled_from([32, 64]),
       causal=st.booleans(),
       window=st.sampled_from([0, 64]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_flash_attention_matches_ref(sq, heads, h, causal, window, dtype):
    nq, nkv = heads
    q = jax.random.normal(jax.random.key(0), (2, sq, nq, h), dtype)
    k = jax.random.normal(jax.random.key(1), (2, sq, nkv, h), dtype)
    v = jax.random.normal(jax.random.key(2), (2, sq, nkv, h), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_causality():
    """Perturbing a future token must not change past outputs."""
    q = jax.random.normal(jax.random.key(0), (1, 256, 4, 32), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (1, 256, 2, 32), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (1, 256, 2, 32), jnp.float32)
    o1 = flash_attention(q, k, v, causal=True, interpret=True)
    k2 = k.at[0, -1].add(10.0)
    v2 = v.at[0, -1].add(10.0)
    o2 = flash_attention(q, k2, v2, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o1[:, :-1]), np.asarray(o2[:, :-1]),
                               atol=1e-6)


# --------------------------------------------------------- selective scan ---
@settings(**SETTINGS)
@given(s=st.sampled_from([64, 128, 192]),
       di=st.sampled_from([32, 64]),
       n=st.sampled_from([4, 16]),
       chunk=st.sampled_from([32, 64]))
def test_selective_scan_matches_ref(s, di, n, chunk):
    ks = jax.random.split(jax.random.key(0), 5)
    x = 0.5 * jax.random.normal(ks[0], (2, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, s, di)))
    a = -jnp.exp(0.3 * jax.random.normal(ks[2], (di, n)))
    b = 0.5 * jax.random.normal(ks[3], (2, s, n))
    c = 0.5 * jax.random.normal(ks[4], (2, s, n))
    d = jnp.ones((di,))
    y1, h1 = selective_scan(x, dt, a, b, c, d, block_d=32, chunk=chunk,
                            interpret=True)
    y2, h2 = selective_scan_ref(x, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-4)


def test_selective_scan_state_carry_equivalence():
    """Scanning [first half] then [second half with h0] == full scan
    (the prefill->decode handoff invariant)."""
    from repro.models.ssm import selective_scan as model_scan
    ks = jax.random.split(jax.random.key(7), 5)
    s, di, n = 128, 32, 8
    x = 0.5 * jax.random.normal(ks[0], (1, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, di)))
    a = -jnp.exp(0.3 * jax.random.normal(ks[2], (di, n)))
    b = 0.5 * jax.random.normal(ks[3], (1, s, n))
    c = 0.5 * jax.random.normal(ks[4], (1, s, n))
    d = jnp.ones((di,))
    y_full, h_full = model_scan(x, dt, a, b, c, d, chunk=32)
    y1, h1 = model_scan(x[:, :64], dt[:, :64], a, b[:, :64], c[:, :64], d,
                        chunk=32)
    y2, h2 = model_scan(x[:, 64:], dt[:, 64:], a, b[:, 64:], c[:, 64:], d,
                        h0=h1, chunk=32)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-5)


def _scan_inputs(seed, s, di, n, bsz=2):
    ks = jax.random.split(jax.random.key(seed), 7)
    x = 0.5 * jax.random.normal(ks[0], (bsz, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, di)))
    a = -jnp.exp(0.3 * jax.random.normal(ks[2], (di, n)))
    b = 0.5 * jax.random.normal(ks[3], (bsz, s, n))
    c = 0.5 * jax.random.normal(ks[4], (bsz, s, n))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (di,))
    h0 = jax.random.normal(ks[6], (bsz, di, n))
    return x, dt, a, b, c, d, h0


@pytest.mark.parametrize("s,di,n", [(37, 64, 16), (333, 32, 4),
                                    (1021, 32, 16)])
def test_selective_scan_kernel_any_length_with_h0(s, di, n):
    """At lengths that are multiples of neither 16 nor 256, from a nonzero
    state: the kernel equals the sequential oracle and the model's chunked
    scan, and its dt = 0 padding leaves h_end bit for bit as it was."""
    from repro.kernels.selective_scan.ops import selective_scan_op
    from repro.models.ssm import selective_scan as model_scan
    x, dt, a, b, c, d, h0 = _scan_inputs(s, s, di, n)
    y1, h1 = selective_scan_op(x, dt, a, b, c, d, h0, block_d=32,
                               interpret=True)
    for y2, h2 in (selective_scan_ref(x, dt, a, b, c, d, h0=h0),
                   model_scan(x, dt, a, b, c, d, h0=h0)):
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   rtol=1e-4, atol=1e-4)
    # 16 more steps of dt = 0 (another padded length) change nothing
    more = lambda t: jnp.pad(t, ((0, 0), (0, 16), (0, 0)))
    y3, h3 = selective_scan_op(more(x), more(dt), a, more(b), more(c), d, h0,
                               block_d=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(h3), np.asarray(h1))
    np.testing.assert_array_equal(np.asarray(y3[:, :s]), np.asarray(y1))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6),
                                       ("bfloat16", 1e-3)])
def test_mamba_prefill_kernel_path_matches_xla(monkeypatch, dtype, tol):
    """mamba_forward(return_state=True) on the TPU path (the platform check
    set to TPU, the kernel interpreted) equals the XLA scan on the CPU:
    output, conv state and SSM state, at an unaligned length and from a
    nonzero incoming state."""
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.kernels.selective_scan.ops import selective_scan_op
    from repro.models import ssm
    from repro.models.model import build_model
    cfg = reduced(get_config("falcon_mamba_7b"), dtype=dtype)
    params = build_model(cfg).init(jax.random.key(0))
    lp = jax.tree.map(lambda t: t[0], params["layers"]["ssm"])
    di, n = lp["a_log"].shape
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (2, 37, cfg.d_model)).astype(dtype)
    state = {"conv": jax.random.normal(ks[1], (2, cfg.ssm.conv_kernel - 1,
                                               di)).astype(dtype),
             "ssm": jax.random.normal(ks[2], (2, di, n))}
    assert not ssm._kernel_scan()
    want, want_state = ssm.mamba_forward(lp, x, cfg, state=state,
                                         return_state=True)
    calls = []

    def interpreted(*args, **kw):
        calls.append(args[0].shape)
        return selective_scan_op(*args, interpret=True, **kw)

    monkeypatch.setattr(ssm, "_kernel_scan", lambda: True)
    monkeypatch.setattr(ssm, "selective_scan_op", interpreted)
    got, got_state = ssm.mamba_forward(lp, x, cfg, state=state,
                                       return_state=True)
    assert calls == [(2, 37, di)]
    f32 = lambda t: np.asarray(t, np.float32)
    # the kernel adds the skip term before rounding to the activation
    # dtype, the XLA path after: in bf16 they differ by its rounding
    scale = np.abs(f32(want)).max()
    np.testing.assert_allclose(f32(got), f32(want), rtol=0, atol=tol * scale)
    np.testing.assert_array_equal(f32(got_state["conv"]),
                                  f32(want_state["conv"]))
    np.testing.assert_allclose(f32(got_state["ssm"]), f32(want_state["ssm"]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend,mesh_size,kernel", [
    ("cpu", None, False), ("tpu", None, True), ("tpu", 1, True),
    ("tpu", 2, False)])
def test_prefill_scan_choice(monkeypatch, backend, mesh_size, kernel):
    """The kernel runs on a TPU and nowhere else, and not under a mesh of
    more than one device, whose d_inner it could not partition."""
    from jax.sharding import AbstractMesh

    from repro.models import ssm
    from repro.parallel.sharding import sharding_context
    monkeypatch.setattr(ssm.jax, "default_backend", lambda: backend)
    mesh = None if mesh_size is None else AbstractMesh((mesh_size,),
                                                       ("model",))
    with sharding_context(mesh):
        assert ssm._kernel_scan() is kernel


# --------------------------------------------------------- decode attn ---
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


@settings(**SETTINGS)
@given(sc=st.sampled_from([128, 256]),
       heads=st.sampled_from([(4, 2), (8, 2), (6, 3)]),
       h=st.sampled_from([32, 64]),
       window=st.sampled_from([0, 64]),
       fill_frac=st.sampled_from([0.25, 1.0]))
def test_decode_attention_matches_ref(sc, heads, h, window, fill_frac):
    nq, nkv = heads
    b = 2
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, nq, h), jnp.float32)
    kc = jax.random.normal(ks[1], (b, sc, nkv, h), jnp.float32)
    vc = jax.random.normal(ks[2], (b, sc, nkv, h), jnp.float32)
    fill = max(1, int(sc * fill_frac))
    cpos = jnp.where(jnp.arange(sc)[None] < fill, jnp.arange(sc)[None], -1)
    cpos = jnp.broadcast_to(cpos, (b, sc)).astype(jnp.int32)
    pos = jnp.full((b,), fill - 1, jnp.int32)
    out = decode_attention(q, kc, vc, cpos, pos, window=window, block_k=64,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, cpos, pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_decode_attention_ignores_empty_slots():
    """Garbage in empty (-1) cache slots must not affect the output."""
    b, sc, nq, nkv, h = 1, 128, 4, 2, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, nq, h), jnp.float32)
    kc = jax.random.normal(ks[1], (b, sc, nkv, h), jnp.float32)
    vc = jax.random.normal(ks[2], (b, sc, nkv, h), jnp.float32)
    cpos = jnp.where(jnp.arange(sc)[None] < 40, jnp.arange(sc)[None], -1)
    cpos = jnp.broadcast_to(cpos, (b, sc)).astype(jnp.int32)
    pos = jnp.full((b,), 39, jnp.int32)
    o1 = decode_attention(q, kc, vc, cpos, pos, interpret=True, block_k=64)
    kc2 = kc.at[:, 40:].add(100.0)
    vc2 = vc.at[:, 40:].add(100.0)
    o2 = decode_attention(q, kc2, vc2, cpos, pos, interpret=True, block_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
