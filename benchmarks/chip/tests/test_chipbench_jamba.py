"""The jamba8.chat_closed cell at test size: its programs keep the names
the trace metrics find, counts_jamba.py against counts made by hand, and
whole CPU runs where ``correct`` comes out true when sound and false under
each fault and for the float8 control."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import CHIP, harness, run_tiny, tiny_cell

import repro.models.model as model_mod
import repro.serving.engine as engine_mod

counts_jamba = harness.sibling("counts_jamba")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one period at widths a CPU test run holds; the router keeps 8 outputs
SMALL = dict(num_hidden_layers=8, num_attention_heads=4,
             num_key_value_heads=2, mamba_dt_rank=4, mamba_d_state=4,
             num_experts=4, router_experts=8, experts_held=[0, 4])


def _cell():
    return tiny_cell("jamba8.chat_closed", **SMALL)


def _program(metric):
    return harness.load_module(CHIP / "metrics" / f"{metric}.py",
                               "chip_metric").PROGRAM


def _name(jitted, *args):
    text = jitted.lower(*args).compile().as_text()
    return harness.sibling("trace").program_name(
        re.match(r"HloModule (\w+)", text).group(1))


def test_programs_keep_their_names():
    from repro.core import PilotSession
    from repro.serving import ServingEngine
    cell = _cell()
    model = cell.driver().program_model(cell.config)
    rows = cell.config["batch_size"]
    with PilotSession() as s:
        (pilot,) = s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, model, batch_size=rows, max_len=32) as eng:
            eng.deploy()
            rt = eng._pilot_runtime(pilot)
            one = eng._prefill_batch(np.zeros((1, 8), np.int32))
            assert _name(rt.prefill, rt.params, one, *rt.load) == _program(
                "serve_prefill_share")
            full = eng._prefill_batch(np.zeros((rows, 8), np.int32))
            _, cache, _ = jax.eval_shape(rt.prefill, rt.params, full,
                                         *rt.load)
            tokens = jax.ShapeDtypeStruct((rows, 1), np.int32)
            positions = jax.ShapeDtypeStruct((rows,), np.int32)
            assert _name(rt.decode, rt.params, cache, tokens, positions,
                         *rt.load) == _program("serve_decode_roofline.jamba")


# a Jamba small enough to count by hand: layer 0 Mamba + dense, layer 1
# attention + experts
TINY = {"hidden_size": 4, "intermediate_size": 6, "mamba_expand": 2,
        "mamba_d_state": 2, "mamba_d_conv": 3, "mamba_dt_rank": 1,
        "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 10,
        "num_hidden_layers": 2, "attn_layer_period": 2,
        "attn_layer_offset": 1, "expert_layer_period": 2,
        "expert_layer_offset": 1, "num_experts": 2, "router_experts": 4,
        "num_experts_per_tok": 2}


def test_counts_by_hand():
    d, f, e, n, k, r, nq, nkv, hd, v = 4, 6, 8, 2, 3, 1, 2, 1, 2, 10
    mamba = (4 * d + 2 * d * 2 * e + (2 * k * e + e) + 2 * e * (r + 2 * n)
             + 4 * (r + 2 * n) + 2 * r * e + (7 * e * n + 2 * e) + 6 * e
             + 2 * e * d + d)
    dense = 4 * d + 6 * d * f + 5 * f + d
    # one held expert a token: top-2 x 2 held / 4
    moe = 4 * d + 2 * d * 4 + 5 * 4 + (6 * d * f + 5 * f + 2 * d) + d

    def attn(keys):
        proj = 2 * d * nq * hd + 2 * 2 * d * nkv * hd + 2 * nq * hd * d
        return 4 * d + proj + nq * keys * (4 * hd + 5) + d
    head = 4 * d + 2 * d * v
    assert counts_jamba.kinds(TINY) == [("ssm", "dense"), ("attn", "moe")]
    assert counts_jamba.token_flops(TINY, 3, True) == (
        mamba + dense + attn(3) + moe + head)
    weights = (2 * (d * 2 * e + k * e + e + e * (r + 2 * n) + r * e + e * d
                    + r + 2 * n) + 4 * (e * n + 2 * e)          # Mamba
               + 2 * 3 * d * f                                 # dense
               + 2 * (d * nq * hd + 2 * d * nkv * hd + nq * hd * d)  # attn
               + 2 * 2 * 3 * d * f + 4 * d * 4                 # 2 experts
               + 2 * 2 * 2 * d                                 # norms
               + 2 * (d + d * v))                              # head
    assert counts_jamba.weight_bytes(TINY) == weights
    b, fill = 3, 5
    flops, nbytes = counts_jamba.decode_step(TINY, b, fill)
    assert flops == b * counts_jamba.token_flops(TINY, fill + 1, True)
    kv = b * (fill + 1) * 2 * nkv * hd * 2
    state = b * (4 * e * n + 2 * (k - 1) * e)
    assert nbytes == weights + 2 * b * d + kv + 2 * state + 2 * b * v
    # a request: prefill of 4, then 2 decode steps at 5 and 6 keys
    want = (counts_jamba.prefill(TINY, 4)[0]
            + counts_jamba.token_flops(TINY, 5, True)
            + counts_jamba.token_flops(TINY, 6, True))
    assert counts_jamba.request_flops(TINY, 4, 3) == pytest.approx(want)
    # prefill attends 1..4 keys: the mean is 2.5
    assert counts_jamba.prefill(TINY, 4)[0] == pytest.approx(
        4 * counts_jamba.token_flops(TINY, 2.5, False) + head)
    # steps of a 4-token prompt with 3 outputs find 4 and 5 tokens cached
    assert counts_jamba.decode_fill([{"prompt": [0] * 4, "out_len": 3}]) \
        == 4.5


def test_jamba2_mini_decode_is_bytes_bound():
    import json
    c = json.loads((CHIP / "configs" / "jamba2_mini_l8.json").read_text())
    # all 14.78 GB of bf16 weights but the embedding table, gathered
    embed = 2 * c["vocab_size"] * c["hidden_size"]
    assert counts_jamba.weight_bytes(c) + embed == pytest.approx(14.78e9,
                                                                 rel=0.001)
    t, bound = counts_jamba.least_time(
        *counts_jamba.decode_step(c, 32, 1100), PEAKS)
    assert bound == "bytes"
    assert 17e-3 < t < 19.5e-3         # 14.8 GB of weights, ~0.6 GB more


def test_balanced_router_is_reproducible_and_rank_one():
    """weights_jamba.make with the configuration: the same seed gives the
    same bits (the driver makes the weights twice: for the engine, then
    for the reference), and the balance takes from each router one
    direction only, leaving every other leaf as drawn."""
    weights_jamba = harness.sibling("weights_jamba")
    c = _cell().config
    model = _cell().driver().program_model(c)
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    raw = weights_jamba.make(abstract, 5)
    bal = weights_jamba.make(abstract, 5, config=c)
    again = weights_jamba.make(abstract, 5, config=c)
    for a, b in zip(jax.tree.leaves(bal), jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)
    routers = 0
    for (path, r), b in zip(jax.tree_util.tree_leaves_with_path(raw),
                            jax.tree.leaves(bal)):
        r, b = np.asarray(r, np.float64), np.asarray(b, np.float64)
        if "router" not in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(r, b)
            continue
        routers += 1
        sv = np.linalg.svd(r - b, compute_uv=False)
        assert sv[1] < 1e-5 * sv[0] and sv[0] > 0
        assert np.linalg.norm(b) < np.linalg.norm(r)
    assert routers == 4


def test_sound_run_is_correct_and_counts():
    out = run_tiny(_cell())
    assert out["correct"], out["checks"]
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_load_imbalance_reads_the_counter():
    read = harness.load_module(CHIP / "metrics" / "moe_load_imbalance.py",
                               "chip_metric").read
    view = harness.View(records={"expert_load": [[4, 4, 4, 4],
                                                 [1, 2, 3, 10]]},
                        trace=None, peaks={}, cell=None)
    assert read(view) == 2.5
    assert read(harness.View(records={}, trace=None, peaks={},
                             cell=None)) is None


def _wrap_decode(mp, change):
    """Jamba's decode with `change` applied to (cache given, logits,
    cache returned), its counts passed on."""
    real = model_mod.build_model

    def build(cfg):
        m = real(cfg)
        dec = m.decode

        def decode(params, cache, tokens, positions, counts=False):
            out = dec(params, cache, tokens, positions, counts=counts)
            return (*change(cache, *out[:2]), *out[2:])
        m.decode = decode
        return m
    mp.setattr(model_mod, "build_model", build)


def _state_unchanged(mp):
    """Decode hands back the cache it was given."""
    _wrap_decode(mp, lambda old, logits, new: (logits, old))


def _half_batch(mp):
    """The second half of the rows gets the first half's logits."""
    def rows_left_out(old, logits, new):
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:logits.shape[0] - half]), new
    _wrap_decode(mp, rows_left_out)


def _token_altered(mp):
    """Every active row's sampled token is the next one up, so that every
    request compared carries the fault."""
    real = engine_mod.sample_tokens

    def altered(logits, active, key, temperature):
        tok, key = real(logits, active, key, temperature)
        return jnp.where(active, (tok + 1) % logits.shape[-1], tok), key
    mp.setattr(engine_mod, "sample_tokens", altered)


JAMBA_FAULTS = {"state_unchanged": _state_unchanged,
                "half_batch": _half_batch,
                "token_altered": _token_altered}


@pytest.mark.parametrize("fault", sorted(JAMBA_FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    JAMBA_FAULTS[fault](monkeypatch)
    out = run_tiny(_cell())
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    """The float8 forward's first choice at each served position, read in
    the float32 reference's logits, held to the cell's own limit of 1.0.
    At this width the program reads 0.04-0.39 and the control 1.27-2.43
    over seeds 3-8 (1.83 and 0.10 at seed 3)."""
    rec = run_tiny(_cell(), control=True)
    assert harness.checks_ok(rec["checks"]), rec["checks"]
    ctl = harness.control_checks(rec["checks"], rec["control"])
    assert not harness.checks_ok(ctl), ctl
