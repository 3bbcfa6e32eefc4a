"""``correct`` is decided by comparisons that fail when they should.

Each run here is a whole run of a cell on the CPU at a size a test run
holds (the chip check skipped, the limits the configuration's own): once
sound, where ``correct`` must come out true, and once with the timed path
broken underneath for each fault the cell can have, where it must come
out false.  The control, the reference in the precision below the
configuration's, must fail its comparison too.  (No cell exchanges data
between chips, so that fault has no case here.)
"""
import numpy as np
import pytest

from chipbench_tiny import harness, run_tiny, tiny_cell

import repro.core.analytics as analytics
import repro.core.mapreduce as mapreduce
import repro.models.model as model_mod
import repro.serving.engine as engine_mod


# -- KMeans --------------------------------------------------------------------
def _kmeans_state_unchanged(mp):
    real = analytics.map_reduce

    def no_update(du, map_fn, reduce_fn, **kw):
        sums, counts, sse = real(du, map_fn, reduce_fn, **kw)
        centroids = np.asarray(kw["extra_args"][0])
        return np.asarray(counts)[:, None] * centroids, counts, sse
    mp.setattr(analytics, "map_reduce", no_update)


def _kmeans_half_batch(mp):
    real = mapreduce._pipeline_fold

    def first_half(du, indices, *a, **kw):
        indices = list(indices)
        return real(du, indices[:max(1, len(indices) // 2)], *a, **kw)
    mp.setattr(mapreduce, "_pipeline_fold", first_half)


def _kmeans_answer_altered(mp):
    real = analytics.kmeans

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.centroids = res.centroids.copy()
        res.centroids[0, 0] += 0.05
        return res
    mp.setattr(analytics, "kmeans", altered)


KMEANS_FAULTS = {"state_unchanged": _kmeans_state_unchanged,
                 "half_batch": _kmeans_half_batch,
                 "answer_altered": _kmeans_answer_altered}


def test_kmeans_sound_run_is_correct():
    out = run_tiny(tiny_cell("kmeans_i.resident"))
    assert out["correct"], out["checks"]
    assert out["metrics"]["kmeans_iter_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(KMEANS_FAULTS))
def test_kmeans_fault_is_not_correct(fault, monkeypatch):
    KMEANS_FAULTS[fault](monkeypatch)
    out = run_tiny(tiny_cell("kmeans_i.resident"))
    assert not out["correct"], out["checks"]


def test_kmeans_control_is_not_correct():
    """Lloyd's at ``high`` (three bf16 passes) against float64, held to
    the limits set for ``highest``."""
    rec = run_tiny(tiny_cell("kmeans_i.resident", points=200_000, clusters=50),
                   seconds=0.5, control=True)
    assert harness.checks_ok(rec["checks"]), rec["checks"]
    ctl = harness.control_checks(rec["checks"], rec["control"])
    assert not harness.checks_ok(ctl), ctl


# -- serving -------------------------------------------------------------------
def _wrap_decode(mp, change):
    real = model_mod.build_model

    def build(cfg):
        m = real(cfg)
        dec = m.decode
        m.decode = lambda params, cache, tokens, positions: change(
            cache, *dec(params, cache, tokens, positions))
        return m
    mp.setattr(model_mod, "build_model", build)


def _serve_state_unchanged(mp):
    _wrap_decode(mp, lambda old, logits, new: (logits, old))


def _serve_half_batch(mp):
    def rows_left_out(old, logits, new):
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:logits.shape[0] - half]), new
    _wrap_decode(mp, rows_left_out)


def _serve_token_altered(mp):
    real = engine_mod.sample_tokens

    def altered(logits, active, key, temperature):
        tok, key = real(logits, active, key, temperature)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1]), key
    mp.setattr(engine_mod, "sample_tokens", altered)


SERVE_FAULTS = {"state_unchanged": _serve_state_unchanged,
                "half_batch": _serve_half_batch,
                "token_altered": _serve_token_altered}


def test_serving_sound_run_is_correct():
    out = run_tiny(tiny_cell("mamba8.chat_closed"))
    assert out["correct"], out["checks"]
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
def test_serving_fault_is_not_correct(fault, monkeypatch):
    SERVE_FAULTS[fault](monkeypatch)
    out = run_tiny(tiny_cell("mamba8.chat_closed"))
    assert not out["correct"], out["checks"]


def test_serving_control_is_not_correct():
    """The float8 forward's first choice at each served position, read in
    the float32 reference's logits.  At this width the control's gap reads
    0.16-0.50 and the program's at most 0.03 (seeds 3-5), both under the
    chip's limit, so the limit here is 0.1, between the two."""
    rec = run_tiny(tiny_cell("mamba8.chat_closed", traffic="chat_open",
                             limits={"logit_gap": 0.1}),
                   seconds=1.5, control=True)
    assert harness.checks_ok(rec["checks"]), rec["checks"]
    ctl = harness.control_checks(rec["checks"], rec["control"])
    assert not harness.checks_ok(ctl), ctl
