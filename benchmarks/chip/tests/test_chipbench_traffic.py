"""The one traffic generator: the same seed gives the same inputs, and
every seed the same sizes in another order."""
from collections import Counter
from itertools import islice

import numpy as np
import pytest

from chipbench_tiny import harness

traffic = harness.sibling("traffic")
MIXES = ["chat_closed", "chat_open"]


def _mix(name):
    return harness.load_mix(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    seed = 2 ** 31 + 12345            # larger than 32 signed bits
    a = list(islice(traffic.requests(mix, seed, 65024), 100))
    b = list(islice(traffic.requests(mix, seed, 65024), 100))
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.gap_s) == (y.max_new_tokens, y.gap_s)
    c = list(islice(traffic.requests(mix, seed + 1, 65024), 100))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_sizes_per_block(name):
    mix = _mix(name)
    n = mix["block"] * 3
    for key in (lambda r: (len(r.prompt), r.max_new_tokens),
                lambda r: round(r.gap_s, 12)):
        got = [Counter(key(r) for r in islice(traffic.requests(mix, s, 100), n))
               for s in (1, 7, 2 ** 40 + 3)]
        assert got[0] == got[1] == got[2]
    orders = [[len(r.prompt) for r in islice(traffic.requests(mix, s, 100), n)]
              for s in (1, 7)]
    assert orders[0] != orders[1]


def test_mix_proportions_and_lengths():
    """A block holds the stratified quantiles of the sourced lognormal
    lengths: the published medians, every prompt length once."""
    mix = _mix("chat_closed")
    assert "arXiv:2311.18677" in mix["source"]
    reqs = list(islice(traffic.requests(mix, 5, 100), mix["block"]))
    prompts = sorted(len(r.prompt) for r in reqs)
    assert prompts == traffic.prompt_lengths(mix)
    assert len(set(prompts)) == mix["block"]
    assert np.median(prompts) == pytest.approx(1020, rel=0.02)
    outs = sorted(r.max_new_tokens for r in reqs)
    assert outs[0] >= 16 and outs[-1] <= 1024
    assert np.median(outs) == pytest.approx(129, rel=0.05)
    # prompt and output lengths are paired by a permutation, not by rank
    pairs = [(len(r.prompt), r.max_new_tokens) for r in reqs]
    assert np.corrcoef(np.array(pairs).T)[0, 1] < 0.5
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 100
               for r in reqs)


def test_mixes_share_their_lengths():
    closed, open_ = _mix("chat_closed"), _mix("chat_open")
    for key in ("source", "prompt", "output", "block"):
        assert closed[key] == open_[key]


def test_open_loop_gaps_keep_the_rate():
    mix = _mix("chat_open")
    reqs = list(islice(traffic.requests(mix, 9, 100), mix["block"] * 4))
    mean = np.mean([r.gap_s for r in reqs])
    assert mean == pytest.approx(1.0 / mix["rate_per_s"], rel=0.05)


def test_points_deterministic():
    centres = np.random.default_rng(0).normal(size=(5, 8)).astype(np.float32)
    a = traffic.points(1000, centres, 0.15, 2 ** 33)
    b = traffic.points(1000, centres, 0.15, 2 ** 33)
    assert a.dtype == np.float32 and a.shape == (1000, 8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.points(1000, centres, 0.15, 1))
    nearest = np.argmin(((a[:, None] - centres[None]) ** 2).sum(-1), axis=1)
    assert np.abs(a - centres[nearest]).max() < 0.15 * 6
    assert traffic.seed32(2 ** 40, "w") == traffic.seed32(2 ** 40, "w")
    assert 0 <= traffic.seed32(2 ** 40, "w") < 2 ** 31
