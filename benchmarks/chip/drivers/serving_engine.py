"""Greedy LM serving through ``ServingEngine.submit`` on a ``PilotSession``
with one one-chip pilot per replica.

Set-up makes the weights on the device from the seed, deploys the engine
and warms, through the engine itself, every shape the cell's traffic
uses: the decode step and one batch-1 refill prefill per prompt length
the mix sends, on every replica, until a whole pass compiles nothing,
after the engine's opening fill (see ``warm``).

The window drives the traffic mix: a closed loop of ``clients`` (each
sends its next request when the last completes) or an open loop of
arrivals on a schedule, each request timed from when it was due.  Once
the window has closed, a sample of the finished requests, drawn from the
seed and holding the longest, is replayed through the plain float32
reference (reference/mamba1.py) and the widest gap by which a served
token's logit lies below the reference's best is compared with its limit.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

import harness

traffic = harness.sibling("traffic")
weights = harness.sibling("weights")
mamba1 = harness.sibling("reference/mamba1")

POLL_S = 0.002          # the client loop's wake-up period
GRACE_S = 60.0          # how long past the close due requests are awaited
WARM_PASSES = 5
WAVE_TOKENS = 4096      # rows x prompt length of the engine's opening fill


def program_model(c: Dict):
    """The program's model at the configuration file's sizes."""
    from repro.configs import get_config
    from repro.models.model import build_model
    base = get_config(c["program_config"])
    cfg = dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        vocab_size=c["vocab_size"], norm_eps=c["layer_norm_epsilon"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["dtype"],
        ssm=dataclasses.replace(
            base.ssm, state_dim=c["state_size"], conv_kernel=c["conv_kernel"],
            expand=c["intermediate_size"] // c["hidden_size"],
            dt_rank=c["time_step_rank"]))
    if cfg.ssm.expand * cfg.d_model != c["intermediate_size"]:
        raise harness.BenchError("intermediate_size is not a multiple of "
                                 "hidden_size")
    return build_model(cfg)


def _compiles(ctx) -> int:
    return 0 if ctx.clock is None else ctx.clock.current["compiles"]


def warm(eng, ctx, mix: Dict, replicas: int, vocab: int) -> int:
    """Open the engine with one short request per replica, then send every
    prompt length to every replica until a whole pass compiles nothing;
    returns the passes made.

    The engine's first fill prefills all of its rows at the first
    prompt's length, and at published widths 32 rows of 256 tokens need
    more than a v5e's 16 GB (32 x 128 takes 13.7 GB), so the opening
    request holds WAVE_TOKENS over the batch, or the shortest prompt if
    that is less; every later fill is a batch-1 refill, the path the
    window drives."""
    r = np.random.default_rng(0)

    def send(lengths):
        reqs = [eng.submit(r.integers(0, vocab, size=length, dtype=np.int32),
                           2)
                for length in lengths for _ in range(replicas)]
        eng.drain(timeout=600)
        for q in reqs:
            q.result(0)

    lengths = traffic.prompt_lengths(mix)
    send([max(1, min(WAVE_TOKENS // eng.batch_size, lengths[0]))])
    for n in range(1, WARM_PASSES + 1):
        before = _compiles(ctx)
        send(lengths)
        if n > 1 and _compiles(ctx) == before:
            return n
    raise harness.BenchError(f"warm-up still compiling after {WARM_PASSES} "
                             f"passes")


def closed_loop(eng, stream, clients: int, seconds: float):
    import jax
    annotate = jax.profiler.TraceAnnotation
    records: List[Dict] = []

    def send():
        rq = next(stream)
        now = time.perf_counter()
        with annotate("bench.submit"):
            handle = eng.submit(rq.prompt, rq.max_new_tokens)
        rec = {"rq": rq, "handle": handle, "due": now}
        records.append(rec)
        return rec

    t0 = time.perf_counter()
    t_end = t0 + seconds
    inflight = [send() for _ in range(clients)]
    while time.perf_counter() < t_end:
        nxt = []
        for rec in inflight:
            if not rec["handle"].done:
                nxt.append(rec)
            elif time.perf_counter() < t_end:
                nxt.append(send())
        inflight = nxt
        with annotate("bench.client_wait"):
            time.sleep(POLL_S)
    return records, t0, t_end, 0.0


def open_loop(eng, stream, seconds: float):
    import jax
    annotate = jax.profiler.TraceAnnotation
    records: List[Dict] = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    due = t0
    late = 0.0
    while True:
        rq = next(stream)
        due += rq.gap_s
        if due >= t_end:
            break
        wait = due - time.perf_counter()
        if wait > 0:
            with annotate("bench.client_wait"):
                time.sleep(wait)
        now = time.perf_counter()
        late = max(late, now - due)
        with annotate("bench.submit"):
            handle = eng.submit(rq.prompt, rq.max_new_tokens)
        records.append({"rq": rq, "handle": handle, "due": due})
    return records, t0, t_end, late


def _sample(done: List[Dict], seed: int, tokens: int, most: int) -> List[Dict]:
    """The longest finished request, then one from each other replica, then
    others, in an order drawn from the seed, until `tokens` served tokens
    or `most` requests."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    rest = [int(i) for i in traffic.rng(seed, "sample").permutation(len(done))
            if i != longest]
    seen = {done[longest]["replica"]}
    first = []
    for i in rest:
        if done[i]["replica"] not in seen:
            seen.add(done[i]["replica"])
            first.append(i)
    order = [longest] + first + [i for i in rest if i not in first]
    out, total = [], 0
    for i in order:
        if total >= tokens or len(out) >= most:
            break
        out.append(done[i])
        total += len(done[i]["tokens"])
    return out


def run(ctx: harness.Context) -> dict:
    import jax
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
                            params=weights.make(abstract, wseed,
                                                jax.devices()[0]),
                            batch_size=c["batch_size"], max_len=bucket,
                            temperature=0.0)
        with eng:
            eng.deploy()
            passes = warm(eng, ctx, mix, replicas, vocab)
            counters0 = dict(eng.counters)
            setup_s = time.perf_counter() - ctx.t_start
            with harness.measured_window(ctx):
                if mix["loop"] == "closed":
                    recs, t0, t_end, late = closed_loop(
                        eng, stream, int(mix["clients"]), ctx.seconds)
                else:
                    recs, t0, t_end, late = open_loop(eng, stream,
                                                      ctx.seconds)
                time.sleep(max(0.0, t_end - time.perf_counter()))
                # read at the close: stopping a trace takes seconds, and
                # the decode loops go on meanwhile
                counters = {k: v - counters0[k]
                            for k, v in eng.counters.items()}
            if mix["loop"] == "open":
                stop = time.perf_counter() + GRACE_S
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
    gc.collect()
    ctx.log(f"[serve] {len(requests)} requests, warm-up passes {passes}, "
            f"generator at most {late:.6f} s late, engine counters in the "
            f"window {counters}")

    finished = [q for q in requests if q["ok"]]
    bad = sum(1 for q in finished if len(q["tokens"]) != q["out_len"]
              or not all(0 <= t < vocab for t in q["tokens"]))
    sample = _sample(finished, ctx.seed, c["check_tokens"],
                     c["check_requests"])
    t_check = time.perf_counter()
    params = weights.make(abstract, wseed, jax.devices()[0])
    gap, control_gap = 0.0, 0.0
    for q in sample:
        g = mamba1.served_gaps(params, q["prompt"], q["tokens"], c, bucket,
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
        "attempted": len(due_in_window),
        "failed": sum(1 for q in due_in_window
                      if q["error"] or (mix["loop"] == "open"
                                        and not q["ok"])),
        "memory_peak_bytes": memory, "checks": checks,
    }
    if ctx.control:
        records["control"] = {"logit_gap": control_gap}
    return records
