"""Jamba (configs/jamba2_mini.py) at a small size on the CPU against the
plain float32 reference of the chip benchmark (benchmarks/chip/reference/
jamba.py, loaded by path): one whole 8-layer period of Mamba-1, GQA
attention without rotary embedding, and an 8-expert top-2 router over the
4 experts a layer holds, on seeded random weights."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.jamba2_mini import HF_CONFIG, from_hf
from repro.models import moe as moe_mod
from repro.models.common import rms_norm
from repro.models.model import build_model

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "jamba_reference", ROOT / "benchmarks" / "chip" / "reference" / "jamba.py")
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# one period at small widths; the router keeps 8 outputs, a layer holds 4
SMALL = dict(HF_CONFIG, hidden_size=64, intermediate_size=96,
             mamba_d_state=4, mamba_dt_rank=8, num_attention_heads=4,
             num_key_value_heads=2, num_experts=8, num_hidden_layers=8,
             vocab_size=128)
HELD = (0, 4)


def _program(held=HELD):
    """The program in float32 throughout, so that it can be held to the
    float32 reference closely: what is left is summation order."""
    cfg = dataclasses.replace(from_hf(SMALL, held=held), dtype="float32")
    m = build_model(cfg)
    params = jax.tree.map(lambda t: t.astype(jnp.float32),
                          m.init(jax.random.key(0)))
    return m, params


def _ref_cfg(held=HELD):
    return dict(SMALL, experts_held=list(held))


def _ref_logits(params, seq, positions, held=HELD):
    return np.asarray(ref.logits_at(params, np.asarray(seq), positions,
                                    _ref_cfg(held), bucket=32, out_bucket=8))


def test_prefill_then_decode_matches_reference():
    """Prefill of a prompt, then three decode steps through the hybrid
    cache (one attention layer's K/V beside seven Mamba states), against
    the reference's full forward at every read position.  Tolerance: both
    are float32; the program's chunked scan, query-chunked attention and
    grouped expert matmuls sum in another order than the reference's plain
    loops, which moves logits of size ~3 by about 1e-5."""
    m, params = _program()
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                SMALL["vocab_size"])
    p = 9
    logits, cache, load = m.prefill(params, {"tokens": tokens[:, :p]},
                                    max_len=16, counts=True)
    got, loads = [logits], [load]
    for t in range(p, 12):
        logits, cache, load = m.decode(params, cache, tokens[:, t:t + 1],
                                       jnp.full((2,), t, jnp.int32),
                                       counts=True)
        got.append(logits)
        loads.append(load)
    got = np.stack([np.asarray(g) for g in got], axis=1)   # (2, 4, V)
    for row in range(2):
        want = _ref_logits(params, tokens[row], np.arange(p - 1, 12))
        np.testing.assert_allclose(got[row], want, rtol=0, atol=2e-4)
    # asked, each call counted its assignments to held experts beside the
    # cache: at most top-2 of each token it processed, in every expert
    # layer; unasked, it returns the logits and the cache alone
    assert all(ld.shape == m.expert_load == (4, 4) for ld in loads)
    assert set(cache) == {"layers"}
    assert int(loads[0].sum(axis=1).max()) <= 2 * 2 * p
    assert all(int(ld.sum(axis=1).max()) <= 2 * 2 for ld in loads[1:])
    assert len(m.decode(params, cache, tokens[:, -1:],
                        jnp.full((2,), 12, jnp.int32))) == 2


def _close(got, want):
    """Equal up to float32 summation order: 1e-5 of the largest output."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=1e-5 * np.abs(want).max())


def _moe_layer(params, layer=1):
    lp = params["layers"][layer]
    assert "moe" in lp
    return lp


def test_expert_halves_add_up_to_the_uncut_layer():
    """The expert-parallel share: the two halves of one expert layer's 8
    experts, each run as the layer that holds it, plus the residual counted
    once, give the uncut reference layer.  Tolerance: float32, summation
    order only, which moves an output by about 1e-6 of the largest."""
    _, params = _program(held=(0, 8))
    lp = _moe_layer(params)
    x = jax.random.normal(jax.random.key(2), (1, 10, 64), jnp.float32)
    h = rms_norm(x, lp["norm2"], SMALL["rms_norm_eps"])
    total = x
    for lo, hi in ((0, 4), (4, 8)):
        cfg = from_hf(SMALL, held=(lo, hi))
        share = dict(lp["moe"], **{w: lp["moe"][w][lo:hi]
                                   for w in ("w_gate", "w_up", "w_down")})
        y, _, _ = moe_mod.dropless_moe(share, h, cfg)
        total = total + y
    want = ref.moe(lp, x[0], _ref_cfg((0, 8)), None)
    _close(total[0], want)


def _forced(params, experts=(0, 1)):
    """One expert layer whose router sends every token to `experts`, and
    inputs that share the direction the router reads."""
    lp = _moe_layer(params)
    u = jax.random.normal(jax.random.key(3), (64,), jnp.float32)
    router = 0.01 * jax.random.normal(jax.random.key(4), (64, 8))
    for rank, e in enumerate(experts):
        router = router.at[:, e].set((2.0 - 0.5 * rank) * u)
    lp = dict(lp, moe=dict(lp["moe"], router=router))
    x = 4.0 * u + 0.3 * jax.random.normal(jax.random.key(5), (1, 24, 64))
    return lp, x


def test_no_token_dropped_under_imbalance():
    """Every token routed to held experts 0 and 1: the dropless layer still
    gives the reference's result (tolerance as above), where the capacity
    dispatch (1.25 x the even share per expert) drops most of them."""
    _, params = _program()
    lp, x = _forced(params)
    cfg = from_hf(SMALL, held=HELD)
    h = rms_norm(x, lp["norm2"], SMALL["rms_norm_eps"])
    y, _, counts = moe_mod.dropless_moe(lp["moe"], h, cfg)
    assert counts.tolist() == [24, 24, 0, 0]
    want = ref.moe(lp, x[0], _ref_cfg(), None)
    _close(x[0] + y[0], want)
    # the capacity path, given the same four experts as the whole router,
    # drops what does not fit
    capped = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=4, held=(), dropless=False))
    lp4 = dict(lp["moe"], router=lp["moe"]["router"][:, :4])
    y_cap, _ = moe_mod.moe_ffn(lp4, h, capped)
    with pytest.raises(AssertionError):
        _close(x[0] + y_cap[0], want)


def test_held_share_needs_the_dropless_layer():
    """The capacity dispatch routes over every expert it has weights for,
    so a held share of the router's experts is only the dropless layer's;
    that layer's load-balance loss is computed only where asked for."""
    moe = from_hf(SMALL, held=HELD).moe
    with pytest.raises(ValueError, match="dropless"):
        dataclasses.replace(moe, dropless=False)
    _, params = _program()
    cfg = from_hf(SMALL, held=HELD)
    lp = _moe_layer(params)
    h = jax.random.normal(jax.random.key(6), (1, 10, 64), jnp.float32)
    y, aux, counts = moe_mod.dropless_moe(lp["moe"], h, cfg)
    y0, aux0, counts0 = moe_mod.dropless_moe(lp["moe"], h, cfg, aux=False)
    assert float(aux) > 0 and float(aux0) == 0.0
    np.testing.assert_array_equal(y, y0)
    np.testing.assert_array_equal(counts, counts0)


@pytest.mark.parametrize("catalog", [
    # Jamba2-Mini and Jamba2-3B, the values of their config.json
    dict(HF_CONFIG),
    dict(HF_CONFIG, attn_layer_offset=7, attn_layer_period=14,
         hidden_size=2560, intermediate_size=8192, mamba_dt_rank=160,
         num_attention_heads=20, num_experts=1, num_experts_per_tok=1,
         num_hidden_layers=28, num_key_value_heads=1,
         tie_word_embeddings=True),
], ids=["jamba2-mini", "jamba2-3b"])
def test_layer_pattern_is_hf_rule(catalog):
    """Layer kinds follow transformers' ``i % period == offset``."""
    from transformers import JambaConfig
    hf = JambaConfig(**catalog)
    cfg = from_hf(catalog)
    assert [("attention" if cfg.layer_mixer(i) == "attn" else "mamba")
            for i in range(cfg.num_layers)] == hf.layers_block_type
    assert [(cfg.moe.num_experts if cfg.layer_ffn(i) == "moe" else 1)
            for i in range(cfg.num_layers)] == hf.layers_num_experts


def test_published_config():
    cfg = get_config("jamba2_mini")
    assert (cfg.d_model, cfg.d_ff, cfg.resolved_head_dim) == (4096, 14336,
                                                               128)
    assert cfg.moe.held_range() == (0, 16) and not cfg.use_rope
    assert 50e9 < cfg.num_params() < 53e9      # "52B-A12B"


# the parent commit's outputs of the reduced falcon-mamba and mixtral
# (init key 0, tokens from key 1): a change that turned one of the new
# switches on by default, or moved these paths, shows here.  The same
# program on the same XLA gives the same bits; the tolerance only
# absorbs a different summation order across CPU thread counts.
GOLDEN = {
    "falcon_mamba_7b": (6441.71923828125, [-2.265625, 1.53125,
                                           -0.1943359375, -0.4609375],
                        402.1646728515625, [0.8359375, 0.0830078125,
                                            1.4921875, 0.87890625]),
    "mixtral_8x22b": (6516.7763671875, [0.625, 0.64453125, -1.96875,
                                        -0.212890625],
                      397.376220703125, [-0.91796875, 1.171875, -0.7734375,
                                         0.83203125]),
}


@pytest.mark.parametrize("arch", sorted(GOLDEN))
def test_new_switches_default_off(arch):
    cfg = reduced(get_config(arch))
    assert cfg.use_rope and not cfg.attn_layer_period
    assert cfg.ssm is None or not cfg.ssm.inner_norms
    assert cfg.moe is None or (cfg.moe.renormalize and not cfg.moe.dropless)
    m = build_model(cfg)
    assert m.expert_load is None
    p = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    full = np.asarray(m.train_forward(p, {"tokens": toks})["logits"],
                      np.float32)
    _, cache = m.prefill(p, {"tokens": toks[:, :-1]}, max_len=32)
    dec, _ = m.decode(p, cache, toks[:, -1:], jnp.full((2,), 15, jnp.int32))
    dec = np.asarray(dec, np.float32)
    total, last, dec_total, dec_row = GOLDEN[arch]
    np.testing.assert_allclose(np.abs(full).sum(), total, rtol=1e-5)
    np.testing.assert_allclose(full[0, -1, :4], last, rtol=1e-5)
    np.testing.assert_allclose(np.abs(dec).sum(), dec_total, rtol=1e-5)
    np.testing.assert_allclose(dec[1, :4], dec_row, rtol=1e-5)


def test_engine_counts_every_assignment():
    """The engine's routed-assignment counter, added to inside the prefill
    and decode programs: with every expert held, each token a program
    processes (prompt tokens of a fill, every row of a decode step) adds
    top_k assignments to each expert layer."""
    from repro.core import PilotSession
    from repro.serving import ServingEngine
    cfg = from_hf(SMALL)
    m = build_model(cfg)
    prompts = [np.arange(5), np.arange(7) + 3]
    with PilotSession() as s:
        s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, m, batch_size=2, max_len=24) as eng:
            eng.deploy()
            assert eng.expert_load() is None or not eng.expert_load().any()
            first = eng.submit(prompts[0], 4)
            first.result(timeout=120)
            eng.submit(prompts[1], 3).result(timeout=120)
            load = eng.expert_load()
            steps = eng.counters["decode_steps"]
    # the opening wave prefills both rows at the first prompt's length,
    # the second request is a batch-1 refill
    tokens = 2 * len(prompts[0]) + len(prompts[1]) + 2 * steps
    assert load.shape == (4, 8)
    assert load.sum(axis=1).tolist() == [2 * tokens] * 4
