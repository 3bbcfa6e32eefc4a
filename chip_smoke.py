"""Bring-up smoke test: the Pilot substrate's main path on a TPU.

    python chip_smoke.py              # one chip: KMeans, then serving
    python chip_smoke.py --chips 4    # four one-chip pilots against one

Everything runs in this one process, through the entry points a user calls
(``PilotSession``, ``ServingEngine``): a chip belongs to one process, so
nothing here starts a child once JAX is up.  Each phase checks its result
against a reference and any failure exits non-zero.  Without a TPU the
script exits non-zero at once.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.

One chip:
  * KMeans on Pilot-Data Memory, the paper's section 4.3 scenario (i):
    1,000,000 points, D=8, k=50, all eight partitions replicated into the
    pilot's device tier, five Lloyd iterations, checked against a float64
    numpy run from the same initial centroids.
  * Serving falcon-mamba-7b at its published widths (d_model 4096,
    d_inner 8192, state 16, vocab 65024), cut to 8 of its 64 layers, with
    random weights from a seed: 8 greedy requests of 32 new tokens, prompts
    of 32 and 64 tokens, so that both the batched first wave and the
    per-row refill prefill run.  Each first token is checked against a
    direct batch-1 ``model.prefill``.

``--chips 4`` runs only what exists across chips: four pilots that must
lease four distinct chips, the serving phase on four replicas (tokens equal
to a one-replica run) and KMeans with the partitions spread over the four
pilots' device tiers (centroids equal to a one-pilot run).

Timings printed here are smoke timings of one run, not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import PilotSession, make_blobs  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

SEED = 0

# KMeans: the paper's scenario (i) (repro.core.analytics.PAPER_SCENARIOS)
KM_POINTS, KM_K, KM_DIM, KM_PARTS, KM_ITERS = 1_000_000, 50, 8, 8, 5
# The assignment's matmuls run at full float32 precision (a numpy emulation
# of the TPU's default, one bf16 pass, puts this SSE ~2% high), so what is
# left is float32 rounding: the partial sums add in another order than
# float64, and a point almost equidistant from two centroids may be
# assigned to the other.  Those few points move the SSE and the centroids
# by far less than these limits.
KM_SSE_RTOL = 1e-3
KM_CENTROID_ATOL = 1e-2

# serving: falcon-mamba-7b at published widths, depth cut to 8 of 64 layers
# (~1.4 B parameters, ~2.8 GB in bf16) to leave one chip's HBM for the rest
SERVE_LAYERS = 8
SERVE_BATCH, SERVE_MAX_LEN, SERVE_GEN = 4, 512, 32
SERVE_PROMPT_LENS = (32, 32, 32, 32, 64, 64, 64, 64)
# bf16 carries 8 significant bits.  The engine's batched prefill and the
# batch-1 reference may round differently, so a first token may differ
# from the reference argmax only where the reference's top-2 logit gap is
# within 8 bf16 steps of the top logit.
LOGIT_GAP_RTOL = 8 * 2.0 ** -8


class CompileClock:
    """Seconds JAX spent compiling (persistent-cache reads included) and
    how many programs came from the cache, from JAX's own monitoring
    events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.lookups = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's first device is "
                 f"{dev.platform!r}; this smoke test runs only on the chip")
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    return dev


def peak_bytes() -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def pilot_device(pilot):
    (dev,) = pilot.mesh.devices.flat
    return dev


def distinct_devices(pilots) -> None:
    devs = [pilot_device(p) for p in pilots]
    check(len({d.id for d in devs}) == len(pilots),
          f"pilots share chips: {[d.id for d in devs]}")


# -- KMeans ------------------------------------------------------------------
def kmeans_run(points: np.ndarray, n_pilots: int, k: int, parts: int,
               iters: int):
    """KMeans through the session with the partitions replicated into the
    device tiers of `n_pilots` one-chip pilots (split evenly)."""
    with PilotSession() as s:
        pilots = s.add_pilots(n_pilots, num_devices=1, memory_gb=1.0)
        if n_pilots > 1:
            distinct_devices(pilots)
        du = s.data("points", points, parts=parts)
        share = np.array_split(np.arange(parts), n_pilots)
        for p, mine in zip(pilots, share):
            du.replicate_to_pilot(p, parts=[int(i) for i in mine],
                                  tier="device")
        res = s.kmeans(du, k=k, iters=iters, seed=SEED)
        for p, mine in zip(pilots, share):
            check(du.replica_residency(p) == {"device": len(mine)},
                  f"pilot {p.id} holds {du.replica_residency(p)}, "
                  f"wanted all {len(mine)} of its partitions in 'device'")
            for i in mine:
                arr = du.partition_device(int(i), pilot=p)
                check(arr.devices() == {pilot_device(p)},
                      f"partition {i} sits on {arr.devices()}, not on "
                      f"pilot {p.id}'s chip")
    return res


def lloyd_reference(points: np.ndarray, k: int, iters: int):
    """Plain float64 Lloyd's from the initial centroids repro.core.analytics
    .kmeans draws (standard normal, numpy default_rng(seed))."""
    c = np.random.default_rng(SEED).normal(
        size=(k, points.shape[1])).astype(np.float32).astype(np.float64)
    x = points.astype(np.float64)
    x2 = np.sum(x * x, axis=1, keepdims=True)
    sse = []
    for _ in range(iters):
        d2 = x2 - 2.0 * (x @ c.T) + np.sum(c * c, axis=1)[None, :]
        idx = np.argmin(d2, axis=1)
        sse.append(float(d2[np.arange(len(x)), idx].sum()))
        counts = np.bincount(idx, minlength=k)
        sums = np.stack([np.bincount(idx, weights=x[:, j], minlength=k)
                         for j in range(x.shape[1])], axis=1)
        c = c.copy()
        nonempty = counts > 0
        c[nonempty] = sums[nonempty] / counts[nonempty, None]
    return c, sse


def compare_kmeans(res, centroids: np.ndarray, sse: list, what: str) -> None:
    rel = [abs(a - b) / abs(b) for a, b in zip(res.sse_history, sse)]
    dc = float(np.max(np.abs(res.centroids - centroids)))
    print(f"[kmeans] vs {what}: max SSE rel diff {max(rel):.3e} "
          f"(limit {KM_SSE_RTOL}), max centroid diff {dc:.3e} "
          f"(limit {KM_CENTROID_ATOL})", flush=True)
    check(len(res.sse_history) == len(sse), "iteration count differs")
    check(max(rel) <= KM_SSE_RTOL, f"SSE off {what}: {rel}")
    check(dc <= KM_CENTROID_ATOL, f"centroids off {what} by {dc}")


def kmeans_phase(n_pilots: int, points=None, k=KM_K, parts=KM_PARTS,
                 iters=KM_ITERS) -> None:
    if points is None:
        points, _ = make_blobs(KM_POINTS, KM_K, d=KM_DIM, seed=SEED)
    t0 = time.perf_counter()
    res = kmeans_run(points, n_pilots, k, parts, iters)
    print(f"[kmeans] {len(points)} points D={points.shape[1]} k={k} "
          f"parts={parts} pilots={n_pilots} device-resident; smoke timing, "
          f"not a metric: iteration seconds "
          f"{[round(t, 4) for t in res.iter_seconds]} "
          f"(first includes compile), phase {time.perf_counter() - t0:.1f}s",
          flush=True)
    if n_pilots == 1:
        ref_c, ref_sse = lloyd_reference(points, k, iters)
        compare_kmeans(res, ref_c, ref_sse, "float64 numpy Lloyd's")
    else:
        one = kmeans_run(points, 1, k, parts, iters)
        compare_kmeans(res, one.centroids, one.sse_history, "one pilot")
    print(f"[kmeans] ok: SSE {[round(v, 2) for v in res.sse_history]}",
          flush=True)


# -- serving -----------------------------------------------------------------
def serving_config():
    # depth cut only: 8 of falcon-mamba-7b's 64 layers, widths as published
    return dataclasses.replace(get_config("falcon_mamba_7b"),
                               num_layers=SERVE_LAYERS)


def make_prompts(vocab: int, lens=SERVE_PROMPT_LENS):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def serve_run(model, prompts, n_replicas: int, gen: int, batch: int,
              max_len: int):
    """Serve `prompts` on `n_replicas` one-chip pilots; returns each
    request's tokens, the pilot that served it, and the engine stats."""
    with PilotSession() as s:
        pilots = s.add_pilots(n_replicas, num_devices=1, memory_gb=1.0)
        if n_replicas > 1:
            distinct_devices(pilots)
        with ServingEngine(s, model, batch_size=batch, max_len=max_len,
                           temperature=0.0, seed=SEED) as eng:
            eng.deploy()
            reqs = [eng.submit(p, gen) for p in prompts]
            eng.drain(timeout=900)
            tokens = [r.result() for r in reqs]
            served_by = [r.pilot_id for r in reqs]
            stats = eng.stats()
    return tokens, served_by, stats


def first_token_reference(model, prompts, max_len: int):
    """Direct batch-1 prefill of each prompt with the engine's weights
    (same init seed): its argmax, top logit and top-2 gap."""
    params = model.init(jax.random.key(SEED))
    prefill = jax.jit(model.prefill, static_argnums=2)
    out = []
    for p in prompts:
        logits, _ = prefill(params, {"tokens": jnp.asarray(p[None, :])},
                            max_len)
        row = np.asarray(logits[0], dtype=np.float32)
        top2 = np.partition(row, -2)[-2:]
        out.append((int(np.argmax(row)), float(top2[1]),
                    float(top2[1] - top2[0])))
    del params
    return out


def serving_phase(n_replicas: int, cfg=None, prompt_lens=SERVE_PROMPT_LENS,
                  gen: int = SERVE_GEN, batch: int = SERVE_BATCH,
                  max_len: int = SERVE_MAX_LEN) -> None:
    cfg = cfg or serving_config()
    model = build_model(cfg)
    prompts = make_prompts(cfg.vocab_size, prompt_lens)
    t0 = time.perf_counter()
    tokens, served_by, stats = serve_run(model, prompts, n_replicas, gen,
                                         batch, max_len)
    wall = time.perf_counter() - t0
    gc.collect()
    n = len(prompts)
    print(f"[serve] {cfg.name} {cfg.num_layers} layers d_model="
          f"{cfg.d_model} vocab={cfg.vocab_size}: {stats['completed']}/{n} "
          f"requests on {n_replicas} replica(s), {stats['tokens_served']} "
          f"tokens, waves={stats['waves']} refills={stats['refills']}; "
          f"smoke timing, not a metric: {wall:.1f}s incl. deploy and "
          f"compile; peak device bytes {peak_bytes()}", flush=True)
    check(stats["completed"] == n, f"{stats['completed']}/{n} completed")
    check(stats["tokens_served"] == n * gen,
          f"tokens_served {stats['tokens_served']} != {n * gen}")
    for i, t in enumerate(tokens):
        check(len(t) == gen, f"request {i}: {len(t)} tokens, wanted {gen}")
        check(all(0 <= v < cfg.vocab_size for v in t),
              f"request {i}: token out of [0, {cfg.vocab_size})")
    if n_replicas > 1:
        used = sorted(set(served_by))
        print(f"[serve] requests per replica: "
              f"{[served_by.count(p) for p in used]}", flush=True)
        one, _, _ = serve_run(model, prompts, 1, gen, batch, max_len)
        diff = [i for i in range(n) if tokens[i] != one[i]]
        check(not diff, f"requests {diff} differ from the one-replica run")
        print(f"[serve] ok: {n_replicas}-replica tokens equal the "
              f"one-replica run", flush=True)
        return
    # one replica takes more requests than rows, so its rows refill; how
    # four replicas split a burst depends on timing
    check(stats["waves"] >= 1 and stats["refills"] >= 1,
          "both the batched wave and the per-row refill must run")
    ref = first_token_reference(model, prompts, max_len)
    worst = min(gap for _, _, gap in ref)
    for i, (want, top, gap) in enumerate(ref):
        got = tokens[i][0]
        if got != want:
            allowed = gap <= LOGIT_GAP_RTOL * max(1.0, abs(top))
            print(f"[serve] request {i}: first token {got} vs reference "
                  f"{want}, top-2 gap {gap:.4g} (top {top:.4g}) "
                  f"{'within' if allowed else 'beyond'} the bf16 tolerance",
                  flush=True)
            check(allowed, f"request {i}: first token differs from the "
                           f"batch-1 prefill beyond the bf16 tolerance")
    print(f"[serve] ok: first tokens match a direct batch-1 prefill "
          f"(smallest top-2 gap {worst:.4g})", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-pilot phases, on a 4-chip host")
    args = ap.parse_args(argv)
    cache_dir = use_compile_cache()
    clock = CompileClock()
    dev = require_tpu()
    if jax.device_count() < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{jax.device_count()} device(s)")
    t0 = time.perf_counter()
    kmeans_phase(args.chips)
    serving_phase(args.chips)
    print(f"[compile] {clock.seconds:.1f}s compiling "
          f"(persistent-cache hits {clock.hits} of {clock.lookups} lookups, "
          f"cache {cache_dir}); run {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
