"""The one traffic generator.  A mix is a JSON file of parameters under
``traffic/``; this module turns it and ``--seed`` into requests or jobs.

Every seed gets the same set of sizes and gaps, in another order: sizes are
drawn by stratified quantiles in blocks of ``block`` requests, and each
block is shuffled by the seed.  So a window that consumes some blocks sees
the mix's proportions exactly whatever the seed, and runs differ by order
and token content alone.

Request mix keys (a job mix holds only what its driver reads):
  source        where the lengths come from
  loop          "closed" (``clients`` each send the next request when the
                last completes) or "open" (arrivals at ``rate_per_s``)
  prompt, output   {"median", "sigma", "min", "max"}: lognormal lengths in
                tokens, clipped to [min, max]
  block         requests per stratified block; a block holds the
                ``block`` stratified quantiles of each length, prompts
                paired with outputs by one fixed permutation
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per use of the seed (any size of int)."""
    salt = int.from_bytes(stream.encode(), "little") % (2 ** 63)
    return np.random.default_rng([int(seed) % (2 ** 64), salt])


def seed32(seed: int, stream: str) -> int:
    """A 31-bit seed for an API that takes no more (jax.random.key)."""
    return int(rng(seed, stream).integers(0, 2 ** 31 - 1))


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int
    gap_s: float             # open loop: seconds after the previous arrival


def _lognormal_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The n stratified quantiles of a lognormal length, as whole tokens."""
    from statistics import NormalDist
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    return np.clip(np.rint(spec["median"] * np.exp(spec["sigma"] * z)),
                   spec["min"], spec["max"]).astype(int)


def _block_sizes(mix: Dict[str, Any]) -> List[tuple]:
    """One block's (prompt_len, output_len) pairs, before shuffling.  The
    pairing permutation is fixed, so it is the same for every seed."""
    n = int(mix["block"])
    prompt = _lognormal_quantiles(mix["prompt"], n)
    out = _lognormal_quantiles(mix["output"], n)[rng(0, "pairing")
                                                 .permutation(n)]
    return list(zip(prompt.tolist(), out.tolist()))


def prompt_lengths(mix: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix sends, shortest first."""
    return sorted({p for p, _ in _block_sizes(mix)})


def _block_gaps(mix: Dict[str, Any]) -> np.ndarray:
    """One block's inter-arrival gaps: exponential quantiles at the rate."""
    n = int(mix["block"])
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / float(mix["rate_per_s"])


def requests(mix: Dict[str, Any], seed: int, vocab: int) -> Iterator[Request]:
    """The mix's endless request stream for `seed`."""
    sizes = _block_sizes(mix)
    gaps = _block_gaps(mix) if mix["loop"] == "open" else None
    r_order = rng(seed, "order")
    r_tok = rng(seed, "tokens")
    index = 0
    while True:
        perm = r_order.permutation(len(sizes))
        gperm = r_order.permutation(len(sizes))
        for j, g in zip(perm, gperm):
            plen, olen = sizes[j]
            prompt = r_tok.integers(0, vocab, size=plen, dtype=np.int32)
            yield Request(index, prompt, int(olen),
                          0.0 if gaps is None else float(gaps[g]))
            index += 1


def points(n: int, centres: np.ndarray, spread: float,
           seed: int) -> np.ndarray:
    """KMeans input: n points in Gaussian blobs of `spread` round
    `centres`, each point's blob drawn uniformly (the repo's synthetic
    clustered data)."""
    r = rng(seed, "points")
    labels = r.integers(0, len(centres), size=n)
    noise = r.normal(size=(n, centres.shape[1])).astype(np.float32)
    return (centres[labels] + np.float32(spread) * noise).astype(np.float32)
