"""Serving-path semantics: rolling SWA cache, long multi-step decode,
MLA absorbed decode, continuous batching invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduced
from repro.models.model import build_model


def _greedy_decode(m, params, cache, tokens, start_pos, steps):
    toks = []
    pos = jnp.full((tokens.shape[0],), start_pos, jnp.int32)
    cur = tokens
    for _ in range(steps):
        logits, cache = m.decode(params, cache, cur, pos)
        cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        toks.append(cur)
        pos = pos + 1
    return jnp.concatenate(toks, axis=1), cache


def test_rolling_window_cache_forgets_distant_tokens():
    """Mixtral-style SWA rolling cache: decoding far past the window, the
    prompt's first token must stop influencing the output."""
    cfg = reduced(get_config("mixtral_8x22b"), sliding_window=8, num_layers=2)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    t1 = jax.random.randint(jax.random.key(1), (1, 6), 0, cfg.vocab_size)
    t2 = t1.at[0, 0].set((t1[0, 0] + 1) % cfg.vocab_size)
    out = {}
    for name, toks in (("a", t1), ("b", t2)):
        _, cache = m.prefill(params, {"tokens": toks}, max_len=64)
        # decode 16 steps with FIXED inputs so divergence can only come
        # from the caches (which differ only at position 0)
        fixed = jnp.full((1, 1), 7, jnp.int32)
        logits_seq = []
        pos = jnp.full((1,), 6, jnp.int32)
        c = cache
        for _ in range(16):
            logits, c = m.decode(params, c, fixed, pos)
            logits_seq.append(logits)
            pos = pos + 1
        out[name] = jnp.stack(logits_seq)
    diff = np.asarray(jnp.max(jnp.abs(out["a"] - out["b"]), axis=(1, 2)))
    assert diff[0] > 0          # early steps see position 0 (inside window)
    assert diff[-1] < 1e-5      # beyond the window: fully forgotten


def test_multi_step_decode_matches_full_forward():
    """Greedy 8-step decode == teacher-forced full forward argmaxes."""
    cfg = reduced(get_config("yi_9b"))
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(2), (2, 8), 0, cfg.vocab_size)
    logits, cache = m.prefill(params, {"tokens": prompt}, max_len=32)
    first = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    gen, _ = _greedy_decode(m, params, cache, first, 8, 7)
    seq = jnp.concatenate([prompt, first, gen], axis=1)
    full = m.train_forward(params, {"tokens": seq})["logits"]
    # teacher-forced next-token argmax at each generated position
    for t in range(7):
        pos = prompt.shape[1] + t
        expect = jnp.argmax(full[:, pos], -1)
        np.testing.assert_array_equal(np.asarray(gen[:, t]),
                                      np.asarray(expect))


def test_ssm_decode_long_state_stability():
    """Mamba decode for 64 steps: state stays finite (no blowup)."""
    cfg = reduced(get_config("falcon_mamba_7b"))
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size)
    logits, cache = m.prefill(params, {"tokens": prompt}, max_len=16)
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    gen, cache = _greedy_decode(m, params, cache, cur, 8, 64)
    ssm_state = cache["main"]["ssm"]["ssm"]
    assert bool(jnp.isfinite(ssm_state).all())
    # random-init selective SSMs drift (decay ~exp(-dt|A|) near 1); the
    # invariant is boundedness, not magnitude
    assert float(jnp.abs(ssm_state).max()) < 1e8


def test_decode_kernel_parity_with_jnp_path():
    """decode_kernel=True (Pallas flash-decoding, interpret mode) must match
    the pure-jnp decode path at the full-model level."""
    cfg = reduced(get_config("yi_9b"))
    m_jnp = build_model(cfg)
    m_ker = build_model(dataclasses.replace(cfg, decode_kernel=True))
    params = m_jnp.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    _, cache1 = m_jnp.prefill(params, {"tokens": prompt}, max_len=32)
    _, cache2 = m_ker.prefill(params, {"tokens": prompt}, max_len=32)
    tok = prompt[:, -1:]
    pos = jnp.full((2,), 8, jnp.int32)
    l1, _ = m_jnp.decode(params, cache1, tok, pos)
    l2, _ = m_ker.decode(params, cache2, tok, pos)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), atol=5e-2,
                               rtol=5e-2)


# ---------------------------------------------------------------------------
# ServingEngine on the pilot substrate (PR 9).  A deterministic stub model
# (next token = last token + 1 mod vocab) makes every assertion exact —
# no float tolerance anywhere, so the refill/masking/recovery plumbing is
# tested in isolation from model numerics.
# ---------------------------------------------------------------------------
import tempfile
import time
from types import SimpleNamespace

from repro.core import PilotSession
from repro.core.pilot import State
from repro.serving import ServingEngine


class _StubModel:
    """next = (last + 1) % vocab; cache is a dict with batch axis 0."""

    def __init__(self, vocab=32, delay=0.0):
        self.cfg = SimpleNamespace(name="stub", vocab_size=vocab,
                                   vision_tokens=0, encoder_layers=0)
        self.vocab = vocab
        self.delay = delay

    def init(self, key):
        return {"w": jnp.zeros((4,), jnp.float32)}

    def _step(self, last):
        logits = jax.nn.one_hot((last + 1) % self.vocab, self.vocab) * 100.0
        return logits, {"last": last.astype(jnp.int32).reshape(-1, 1)}

    def _sleep(self):
        time.sleep(self.delay)
        return np.int32(0)

    def prefill(self, params, batch, max_len):
        return self._step(batch["tokens"][:, -1])

    def decode(self, params, cache, tokens, positions):
        tok = tokens[:, 0]
        if self.delay:
            # the engine jits decode; a bare time.sleep would run only at
            # trace time — io_callback makes the delay a runtime effect
            pause = jax.experimental.io_callback(
                self._sleep, jax.ShapeDtypeStruct((), jnp.int32),
                ordered=True)
            tok = tok + pause
        return self._step(tok)


def _expected(prompt, gen, vocab=32):
    return [(int(prompt[-1]) + 1 + i) % vocab for i in range(gen)]


def test_engine_refill_exact_token_counts():
    """More requests than batch rows: freed rows MUST be refilled from the
    queue (the old serve.py never drained pending after the first wave),
    and every request's output must be exact — so a row that serves
    request A then request B can't leak tokens across the splice."""
    model = _StubModel()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 32, size=4 + (i % 3)).astype(np.int32)
               for i in range(6)]
    with PilotSession() as s:
        s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, model, batch_size=2, max_len=32,
                           page_tokens=4) as eng:
            eng.deploy()
            reqs = [eng.submit(p, 5) for p in prompts]
            eng.drain(timeout=60)
            for p, r in zip(prompts, reqs):
                assert r.result(timeout=5) == _expected(p, 5)
            st = eng.stats()
    assert st["completed"] == 6
    assert st["refills"] >= 4          # 6 requests through 2 rows
    assert st["tokens_served"] == 6 * 5  # exact: no padded/retired counting


def test_engine_inactive_rows_do_not_count_tokens():
    """Rows that finished early (short gen) or were padding in a prefill
    wave must stop sampling AND stop counting: tokens_served is exactly
    the sum of requested gen lengths (the old loop kept counting retired
    rows via the `generated[row] = -1e6` hack)."""
    model = _StubModel()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 32, size=4).astype(np.int32)
               for _ in range(3)]
    gens = [2, 9, 5]                   # ragged: rows retire at different steps
    with PilotSession() as s:
        s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, model, batch_size=4, max_len=32,
                           page_tokens=4) as eng:   # batch 4 > 3 requests
            eng.deploy()
            reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
            eng.drain(timeout=60)
            for p, g, r in zip(prompts, gens, reqs):
                got = r.result(timeout=5)
                assert got == _expected(p, g)
                assert len(got) == g   # exactly g — not max(gens), not 0
            st = eng.stats()
    assert st["tokens_served"] == sum(gens)


def test_engine_recovers_requests_after_pilot_kill():
    """Kill a pilot mid-decode (state FAILED + volatile tiers lost, as the
    chaos harness does): its in-flight requests must be recovered from
    the durable KV-page partitions and finish on the surviving replica
    with byte-exact outputs and exact token accounting."""
    model = _StubModel(delay=0.02)     # slow decode so the kill lands mid-run
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 32, size=5).astype(np.int32)
               for _ in range(4)]
    with tempfile.TemporaryDirectory() as ckpt:
        with PilotSession(checkpoint_dir=ckpt, supervise=True) as s:
            pilots = s.add_pilots(2, memory_gb=0.25)
            with ServingEngine(s, model, batch_size=2, max_len=64,
                               page_tokens=4) as eng:
                eng.deploy()
                reqs = [eng.submit(p, 30) for p in prompts]
                time.sleep(0.25)       # let decode get going on both pilots
                # kill a pilot that actually owns in-flight requests, so
                # the recovery path is exercised regardless of routing
                victim = next((rep.pilot for rep in eng._replicas.values()
                               if rep.active), pilots[0])
                victim.state = State.FAILED
                if victim.tier_manager is not None:
                    victim.tier_manager.lose_volatile()
                eng.drain(timeout=120)
                for p, r in zip(prompts, reqs):
                    assert r.result(timeout=10) == _expected(p, 30)
                st = eng.stats()
    assert st["completed"] == 4        # zero data loss
    assert st["recovered_requests"] >= 1
    assert st["replica_deaths"] >= 1


class _CrashingModel(_StubModel):
    """Prefill fails the way a first compile or an out-of-memory does."""

    def prefill(self, params, batch, max_len):
        raise RuntimeError("RESOURCE_EXHAUSTED: stub prefill out of memory")


def test_engine_decode_loop_crash_reaches_the_caller():
    """A decode loop that raises on a still-running pilot is a program
    error: drain() and result() raise it with its own type and message
    well inside the timeout, and the pilot is not re-adopted (it would
    only crash again)."""
    with PilotSession() as s:
        (pilot,) = s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, _CrashingModel(), batch_size=2, max_len=32,
                           page_tokens=4) as eng:
            eng.deploy(reaper_interval_s=0.02)
            req = eng.submit(np.arange(4, dtype=np.int32), 3)
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                eng.drain(timeout=60)
            assert time.monotonic() - t0 < 30
            with pytest.raises(RuntimeError, match="stub prefill"):
                req.result(timeout=1)
            time.sleep(0.2)            # several reaper sweeps
            st = eng.stats()
            assert pilot.state is State.RUNNING     # the pilot is healthy
    assert st["replicas"] == {}        # retired, never re-adopted
    assert st["replica_deaths"] == 1
    assert st["completed"] == 1        # the failed request is finished


def test_engine_deploy_releases_the_init_tree():
    """deploy() keeps no reference to the tree passed as ``params=``: the
    shards are the model from then on, so each replica's runtime is the
    only device copy of the weights (at 14.8 GB a second copy does not fit
    a v5e)."""
    import gc
    import weakref
    model = _StubModel()
    with PilotSession() as s:
        s.add_pilots(1, memory_gb=0.25)
        eng = ServingEngine(s, model, params={"w": jnp.arange(4.0)},
                            batch_size=2, max_len=32)
        leaf = weakref.ref(eng._params["w"])
        with eng:
            eng.deploy()
            gc.collect()
            assert leaf() is None
            assert eng.submit(np.arange(4), 3).result(timeout=60) == [4, 5, 6]
