"""Greedy serving of a hybrid Mamba / attention / expert model (Jamba)
through ``ServingEngine.submit`` on a ``PilotSession``, one one-chip pilot
per replica.

Set-up, the window and the comparison are serving_engine.py's (its
``warm``, ``closed_loop``, ``open_loop`` and ``_sample``); this driver
differs in the model (configs/jamba2_mini.py's ``from_hf`` at the
configuration file's keys, holding ``experts_held`` of the router's
``router_experts``), the weights (weights_jamba.py) and the reference
(reference/jamba.py).  It also records the engine's routed-assignment
counter over the window, read where the engine's counters are read.
"""
from __future__ import annotations

import gc
import resource
import time
from typing import Dict

import harness

traffic = harness.sibling("traffic")
weights_jamba = harness.sibling("weights_jamba")
jamba = harness.sibling("reference/jamba")
serving = harness.load_module(harness.HERE / "drivers" / "serving_engine.py",
                              "chip_driver")


def program_model(c: Dict):
    """The program's model at the configuration file's sizes."""
    try:
        from repro.configs.jamba2_mini import from_hf
    except ImportError as e:
        raise harness.BenchError(f"the program has no Jamba model: {e}")
    from repro.models.model import build_model
    cfg = from_hf(dict(c, num_experts=c["router_experts"]),
                  name=c["name"], held=tuple(c["experts_held"]))
    lo, hi = cfg.moe.held_range()
    if hi - lo != c["num_experts"]:
        raise harness.BenchError("experts_held does not span num_experts")
    return build_model(cfg)


def _rss_gb() -> float:
    """The process's peak resident set so far, in GB (Linux: KiB units)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def run(ctx: harness.Context) -> dict:
    import jax
    import numpy as np
    from repro.core import PilotSession
    from repro.serving import ServingEngine

    c, mix = ctx.cell.config, ctx.cell.mix
    replicas = ctx.cell.chips
    vocab = c["vocab_size"]
    bucket = max(traffic.prompt_lengths(mix)) + mix["output"]["max"]
    model = program_model(c)
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    wseed = traffic.seed32(ctx.seed, "weights")
    stream = traffic.requests(mix, ctx.seed, vocab)
    with PilotSession() as s:
        s.add_pilots(replicas, num_devices=1, memory_gb=c["device_tier_gb"])
        eng = ServingEngine(s, model,
                            params=weights_jamba.make(
                                abstract, wseed, jax.devices()[0], c),
                            batch_size=c["batch_size"], max_len=bucket,
                            temperature=0.0)
        with eng:
            eng.deploy()
            rss_deploy = _rss_gb()
            passes = serving.warm(eng, ctx, mix, replicas, vocab)
            counters0 = dict(eng.counters)
            load0 = eng.expert_load()
            setup_s = time.perf_counter() - ctx.t_start
            with harness.measured_window(ctx):
                if mix["loop"] == "closed":
                    recs, t0, t_end, late = serving.closed_loop(
                        eng, stream, int(mix["clients"]), ctx.seconds)
                else:
                    recs, t0, t_end, late = serving.open_loop(
                        eng, stream, ctx.seconds)
                time.sleep(max(0.0, t_end - time.perf_counter()))
                # read at the close: stopping a trace takes seconds, and
                # the decode loops go on meanwhile
                counters = {k: v - counters0[k]
                            for k, v in eng.counters.items()}
                load = eng.expert_load() - load0
            if mix["loop"] == "open":
                stop = time.perf_counter() + serving.GRACE_S
                for rec in recs:
                    try:
                        rec["handle"].result(max(0.0, stop - time.perf_counter()))
                    except Exception:   # noqa: BLE001 - counted as failed
                        pass
            memory = harness.memory_peak_bytes()
        requests = []
        for rec in recs:
            h = rec["handle"]
            ok = h.done and h.error is None
            requests.append({
                "prompt": rec["rq"].prompt, "out_len": rec["rq"].max_new_tokens,
                "due": rec["due"], "t_done": h.t_done if h.done else None,
                "ok": ok, "error": h.done and h.error is not None,
                "replica": h.pilot_id,
                "tokens": list(h.tokens) if ok else None})
        del eng
    # the engine's runtimes went with it: the reference's copy of the
    # weights is the only one on the chip
    del s
    gc.collect()
    ctx.log(f"[serve] {len(requests)} requests, warm-up passes {passes}, "
            f"generator at most {late:.6f} s late, engine counters in the "
            f"window {counters}, routed assignments per held expert "
            f"{load.tolist()}, host peak RSS at deploy {rss_deploy:.2f} GB")

    finished = [q for q in requests if q["ok"]]
    bad = sum(1 for q in finished if len(q["tokens"]) != q["out_len"]
              or not all(0 <= t < vocab for t in q["tokens"]))
    sample = serving._sample(finished, ctx.seed, c["check_tokens"],
                             c["check_requests"])
    t_check = time.perf_counter()
    params = weights_jamba.make(abstract, wseed, jax.devices()[0], c)
    gap, control_gap = 0.0, 0.0
    for q in sample:
        g = jamba.served_gaps(params, q["prompt"], q["tokens"], c, bucket,
                              mix["output"]["max"], control=ctx.control)
        gap = max(gap, g["gap"])
        control_gap = max(control_gap, g.get("control_gap", 0.0))
    del params
    ctx.log(f"[serve] compared {len(sample)} requests from "
            f"{len({q['replica'] for q in sample})} replica(s), "
            f"{sum(len(q['tokens']) for q in sample)} served tokens in "
            f"{time.perf_counter() - t_check:.3f} s")
    checks = {
        "logit_gap": {"value": gap if sample else float("inf"),
                      "limit": c["limits"]["logit_gap"]},
        "bad_requests": {"value": float(bad), "limit": 0.0},
    }
    due_in_window = [q for q in requests if q["due"] < t_end]
    records = {
        "setup_s": setup_s, "window": (t0, t_end), "window_s": t_end - t0,
        "requests": requests, "chips": replicas, "loop": mix["loop"],
        "generator_late_s": late, "counters": counters,
        "expert_load": np.asarray(load).tolist(),
        "host_rss_deploy_gb": rss_deploy,
        "attempted": len(due_in_window),
        "failed": sum(1 for q in due_in_window
                      if q["error"] or (mix["loop"] == "open"
                                        and not q["ok"])),
        "memory_peak_bytes": memory, "checks": checks,
    }
    if ctx.control:
        records["control"] = {"logit_gap": control_gap}
    return records
