"""KMeans jobs through ``PilotSession.kmeans`` over points held in the
pilots' device tiers, one job at a time, back to back for the window.
The points lie in blobs round the program's own start centroids, so each
job re-fits the clusters from their true centres, as a re-clustering
warm-started from the last model does.

Set-up makes the points from the seed, provisions the pilots, replicates
every partition into their device tiers and runs one whole job, which
compiles every program the window runs.  Each job in the window restarts
from the same centroids, so every job's answer is compared with the one
float64 Lloyd's run once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

import harness

traffic = harness.sibling("traffic")
lloyd = harness.sibling("reference/lloyd")


def run(ctx: harness.Context) -> dict:
    import jax
    from repro.core import PilotSession

    c, mix = ctx.cell.config, ctx.cell.mix
    n, k, d, parts = c["points"], c["clusters"], c["dim"], c["partitions"]
    iters = int(mix["iters_per_job"])
    # every job re-fits from the clusters' own centres: the program's start
    # (kmeans(seed=...)) is where the points were drawn round
    init_seed = traffic.seed32(ctx.seed, "centroids")
    start = lloyd.initial_centroids(init_seed, k, d)
    points = traffic.points(n, start, c["blob_spread"], ctx.seed)
    jobs = []
    with PilotSession() as s:
        pilots = s.add_pilots(c["pilots"], num_devices=1,
                              memory_gb=c["device_tier_gb"])
        du = s.data("points", points, parts=parts)
        share = np.array_split(np.arange(parts), len(pilots))
        for p, mine in zip(pilots, share):
            du.replicate_to_pilot(p, parts=[int(i) for i in mine],
                                  tier="device")
        for p, mine in zip(pilots, share):
            held = du.replica_residency(p)
            if held != {"device": len(mine)}:
                raise harness.BenchError(
                    f"pilot {p.id} holds {held}, not all {len(mine)} of "
                    f"its partitions in its device tier")
        s.kmeans(du, k=k, iters=iters, seed=init_seed)
        setup_s = time.perf_counter() - ctx.t_start
        with harness.measured_window(ctx):
            t0 = time.perf_counter()
            t_end = t0 + ctx.seconds
            while True:
                with jax.profiler.TraceAnnotation("bench.kmeans_job"):
                    res = s.kmeans(du, k=k, iters=iters, seed=init_seed)
                jobs.append((res.centroids, list(res.sse_history)))
                if time.perf_counter() >= t_end:
                    break
            window_s = time.perf_counter() - t0
        memory = harness.memory_peak_bytes()
    t_check = time.perf_counter()
    ref_c, ref_sse = lloyd.lloyd_f64(points, start, iters)
    readings = lloyd.readings(jobs, ref_c, ref_sse)
    ctx.log(f"[kmeans] {len(jobs)} jobs of {iters} iterations compared with "
            f"float64 Lloyd's in {time.perf_counter() - t_check:.3f} s")
    records = {
        "setup_s": setup_s, "window_s": window_s,
        "iterations": len(jobs) * iters, "jobs": len(jobs),
        "attempted": len(jobs), "failed": 0,
        "memory_peak_bytes": memory,
        "checks": {name: {"value": v, "limit": c["limits"][name]}
                   for name, v in readings.items()},
    }
    if ctx.control:
        ctl = lloyd.lloyd_high(points, start, iters)
        records["control"] = lloyd.readings([ctl], ref_c, ref_sse)
    return records
