"""Serving CLI: continuous-batching LM serving ON the pilot substrate.

    python -m repro.launch.serve --arch llama3_2_1b --preset 20m \
        --requests 32 --batch 8 --gen 64 --pilots 2

This used to be a standalone driver that ran *beside* the pilot system
(params and KV state in loop locals, no scheduler, no recovery) — and
its continuous-batching loop was broken: finished rows were never
refilled with pending prompts, and retired/padded rows kept sampling and
counting as served tokens.  It is now a thin CLI over
``repro.serving.ServingEngine`` (see that module): model shards and
KV-cache pages are tiered Pilot-Data partitions, requests route
replica-aware through the ``SchedulingPolicy``, each pilot runs its
decode loop as a long-lived resident task, and — with ``--supervise``
and a ``--checkpoint-dir`` — a pilot killed mid-stream has its in-flight
requests recovered from the durable tier.

Migration: all the old flags work unchanged; the old single-pilot
behavior is ``--pilots 1`` (the default).  Programmatic users of
``main()`` now get the engine's stats dict back instead of the median
decode-step time.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import PilotSession
from repro.launch.train import scaled_config
from repro.models.model import build_model
from repro.serving import ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--preset", default="20m")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pilots", type=int, default=1,
                    help="serving replicas (pilots) in the session")
    ap.add_argument("--memory-gb", type=float, default=0.5,
                    help="managed memory per pilot (shard + page tiers)")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="KV-page flush granularity in generated tokens")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="durable tier for shards + KV pages (enables "
                         "recovery of in-flight requests)")
    ap.add_argument("--supervise", action="store_true",
                    help="self-healing session: quarantine/respawn dead "
                         "pilots mid-stream")
    args = ap.parse_args(argv)

    cfg = scaled_config(args.arch, args.preset)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=args.prompt_len).astype(np.int32)
               for _ in range(args.requests)]

    with PilotSession(checkpoint_dir=args.checkpoint_dir,
                      supervise=args.supervise) as session:
        # each replica leases its own share of the chips; asking every
        # pilot for all of them would put every replica on the same ones
        session.add_pilots(args.pilots,
                           num_devices=max(1, jax.device_count()
                                           // args.pilots),
                           memory_gb=args.memory_gb, affinity="server")
        engine = ServingEngine(
            session, model, batch_size=args.batch, max_len=args.max_len,
            temperature=args.temperature, page_tokens=args.page_tokens)
        with engine:
            engine.deploy()
            t0 = time.perf_counter()
            reqs = [engine.submit(p, args.gen) for p in prompts]
            engine.drain(timeout=600)
            wall = time.perf_counter() - t0
            stats = engine.stats()
            for r in reqs:
                assert len(r.result()) == args.gen
        steps = max(1, stats["decode_steps"])
        print(f"[serve] {cfg.name}: {stats['completed']}/{args.requests} "
              f"requests on {args.pilots} pilot(s) in {wall:.1f}s; "
              f"{stats['tokens_served']} tokens "
              f"({stats['tokens_served'] / wall:.0f} tok/s, "
              f"{wall / steps * 1e3:.1f}ms/step), "
              f"p99 latency {stats['p99_latency_s'] * 1e3:.0f}ms, "
              f"refills={stats['refills']}, "
              f"recovered={stats['recovered_requests']}")
        return stats


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
