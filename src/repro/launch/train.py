"""End-to-end training driver on the Pilot stack.

    python -m repro.launch.train --arch llama3_2_1b --preset 100m \
        --steps 300 --batch 8 --seq 512

Flow (paper Fig. 3): corpus lives as a file-tier DataUnit -> staged to the
host tier by the pipeline -> batches feed the jitted train_step running on a
PilotCompute that retains the mesh + compiled step across the whole run ->
checkpoints write back to the persistent tier asynchronously. --failure-at
injects a simulated pilot loss to demonstrate checkpoint/restart recovery.

Presets scale the *width/depth* of the chosen architecture family while
keeping its structure (GQA ratios, MoE top-k, SSM dims), so every assigned
arch has a runnable small variant: smoke (~1M), 20m, 100m (the e2e target).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig, reduced
from repro.core import (ComputeDataManager, DataUnit, PilotComputeDescription,
                        PilotComputeService, make_backend)
from repro.data.pipeline import BatchPipeline, corpus_data_unit
from repro.models.common import param_count, param_pspecs
from repro.models.model import build_model
from repro.parallel.sharding import AxisRules, sharding_context
from repro.train import steps as steps_mod
from repro.train.steps import TrainState

PRESETS = {
    "smoke": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=512, head_dim=16),
    "20m": dict(num_layers=4, d_model=384, num_heads=6, num_kv_heads=2,
                d_ff=1024, vocab_size=8192, head_dim=64),
    "100m": dict(num_layers=8, d_model=768, num_heads=12, num_kv_heads=4,
                 d_ff=2048, vocab_size=16384, head_dim=64),
    "full": {},
}


def scaled_config(arch: str, preset: str) -> ModelConfig:
    cfg = get_config(arch)
    if preset == "full":
        return cfg
    if preset == "smoke":
        return reduced(cfg)
    over = dict(PRESETS[preset])
    if cfg.is_moe:
        over["moe"] = dataclasses.replace(
            cfg.moe, num_experts=min(cfg.moe.num_experts, 8),
            expert_d_ff=over["d_ff"],
            first_k_dense=min(cfg.moe.first_k_dense, 1),
            first_dense_d_ff=over["d_ff"])
        over["d_ff"] = cfg.d_ff and over["d_ff"]
    if cfg.ssm is not None:
        over["ssm"] = cfg.ssm
        if cfg.d_ff == 0:
            over["d_ff"] = 0
    if cfg.vision_tokens:
        over["vision_tokens"] = min(cfg.vision_tokens, 16)
        over["vision_embed_dim"] = 128
    if cfg.encoder_layers:
        over["encoder_layers"] = min(cfg.encoder_layers, 4)
        over["encoder_seq_len"] = min(cfg.encoder_seq_len, 64)
    over["global_attn_layers"] = tuple(
        i for i in cfg.global_attn_layers if i < over["num_layers"])
    if cfg.sliding_window:
        over["sliding_window"] = min(cfg.sliding_window, 256)
    over["name"] = f"{cfg.name}-{preset}"
    return dataclasses.replace(cfg, **over)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--preset", default="100m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opt-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--failure-at", type=int, default=0,
                    help="inject a pilot failure at this step (demo)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = scaled_config(args.arch, args.preset)
    model = build_model(cfg)
    n_params = param_count(model.specs)
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"devices={jax.device_count()}")

    # --- pilot: retained resources for the whole run ---
    svc = PilotComputeService()
    pilot = svc.submit_pilot(PilotComputeDescription(
        backend="inprocess", num_devices=jax.device_count(),
        affinity="trainer"))
    manager = ComputeDataManager(svc)
    mesh = pilot.mesh
    rules = AxisRules()

    # --- data: file tier -> host tier -> batches ---
    backends = {"file": make_backend("file", root=str(Path(args.ckpt_dir) / "corpus")),
                "host": make_backend("host")}
    du = corpus_data_unit("corpus", cfg,
                          num_tokens=max(2_000_000, 4 * args.batch
                                         * (args.seq + 1) * 16),
                          backends=backends, tier="file")
    du.to_tier("host", delete_source=False)
    pipe = BatchPipeline(du, cfg, args.batch, args.seq)

    # --- jitted step with shardings resolved from the rules table ---
    pcfg = ParallelConfig(microbatches=args.microbatches,
                          opt_state_dtype=args.opt_dtype)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(10, args.steps // 20))
    step_fn = steps_mod.make_train_step(model, pcfg, tcfg)

    def jit_step():
        def fn(state, batch):
            with sharding_context(mesh, rules):
                return step_fn(state, batch)
        return jax.jit(fn, donate_argnums=(0,))

    jitted = pilot.jit_cached(("train_step", cfg.name), jit_step)
    state = steps_mod.init_train_state(model, jax.random.key(tcfg.seed), pcfg)
    ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name)

    start = 0
    if ckpt.latest_step() is not None:
        state, start = ckpt.restore(state)
        print(f"[train] restored step {start}")

    t_hist = []
    failed_once = False
    step = start
    while step < args.steps:
        batch = next(pipe)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if args.failure_at and step == args.failure_at and not failed_once:
            failed_once = True
            print(f"[train] !!! injecting pilot failure at step {step}")
            svc.release(pilot)
            pilot = svc.submit_pilot(PilotComputeDescription(
                backend="inprocess", num_devices=jax.device_count(),
                affinity="trainer"))
            mesh = pilot.mesh
            jitted = pilot.jit_cached(("train_step", cfg.name), jit_step)
            state, step = ckpt.restore(state)
            print(f"[train] recovered at step {step}")
            continue
        t0 = time.time()
        cu = manager.run(lambda s=state, b=batch: jitted(s, b),
                         affinity="trainer")
        state, metrics = cu.result()
        metrics["loss"].block_until_ready()
        dt = time.time() - t0
        t_hist.append(dt)
        step += 1
        if step % args.log_every == 0 or step == 1:
            print(f"[train] step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
        if step % args.ckpt_every == 0:
            ckpt.save(step, state, blocking=False)
    ckpt.save(args.steps, state, blocking=True)
    pipe.close()
    svc.cancel_all()
    med = float(np.median(t_hist)) if t_hist else 0.0
    tokens_s = args.batch * args.seq / med if med else 0.0
    print(f"[train] done: median step {med*1e3:.0f}ms, {tokens_s:.0f} tok/s, "
          f"final loss {float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
