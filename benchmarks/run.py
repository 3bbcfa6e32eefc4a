"""Benchmark harness: one module per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit) and
writes the machine-readable records (per-benchmark wall time, bytes staged,
evictions) to a JSON artifact (default ``BENCH_pr10.json``; override with
``--json PATH``) so the perf trajectory is tracked across PRs.

``--quick`` is the CI smoke path: it runs the tiering, map_reduce,
multi-pilot, checkpoint, session, throughput, resilience, and transport
benches,
writes the artifact, and exits non-zero if the pipelined map_reduce
engine is slower than the sequential baseline, the 2-pilot distributed
Pilot-Data run is below 1.3x the single-pilot wall clock on the
2x-over-budget workload, the 3x-over-budget checkpoint-tier workload
fails to complete / loses to naive re-staging from the original file
store, cost-modelled cross-pilot sibling reads fail to beat re-pulling
from a simulated slow home store, the batched task engine misses its
>=10^5 tasks/s and >=20x-over-per-CU throughput floor, or the chaos
kill-one-of-N resilience storm loses data / fails to restore
replication / exceeds 1.5x the fault-free wall time, or the zero-copy
plane misses its >= 3x view-over-copy fetch floor / regresses the
steady-state map_reduce past the copy-mode baseline, or substrate LM
serving exceeds 1.5x the isolated stack's p99 / loses requests or
token-count exactness under the chaos kill, or the elastic autoscaler
fails to beat the static-small fleet >= 1.2x under burst / loses a
partition on scale-in / executes unpriced or quarantine-touching
rebalance migrations.
"""
from __future__ import annotations

import sys
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

DEFAULT_JSON = "BENCH_pr10.json"
MULTIPILOT_MIN_SPEEDUP = 1.3
CHECKPOINT_MIN_SPEEDUP = 1.0
SESSION_MIN_SPEEDUP = 1.5


def _json_path(argv) -> str:
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 < len(argv):
            return argv[i + 1]
    return DEFAULT_JSON


def _gate(records) -> None:
    """CI guardrails: the pipelined engine must not lose to sequential, and
    2 pilots must beat 1 pilot >= 1.3x on the over-budget workload."""
    rows = {r["name"]: r for r in records}
    pipe = rows.get("bench_mapreduce.pipelined")
    if pipe is None:
        print("bench gate: no bench_mapreduce.pipelined record",
              file=sys.stderr)
        raise SystemExit(1)
    if pipe.get("speedup", 0.0) < 1.0:
        print(f"bench gate: pipelined map_reduce slower than sequential "
              f"({pipe.get('speedup'):.2f}x)", file=sys.stderr)
        raise SystemExit(1)
    mp = rows.get("bench_multipilot.pilots2")
    if mp is None:
        print("bench gate: no bench_multipilot.pilots2 record",
              file=sys.stderr)
        raise SystemExit(1)
    if mp.get("speedup_vs_1", 0.0) < MULTIPILOT_MIN_SPEEDUP:
        print(f"bench gate: 2-pilot map_reduce only "
              f"{mp.get('speedup_vs_1'):.2f}x vs 1 pilot "
              f"(target {MULTIPILOT_MIN_SPEEDUP}x)", file=sys.stderr)
        raise SystemExit(1)
    ck = rows.get("bench_checkpoint.tiered")
    if ck is None:
        print("bench gate: no bench_checkpoint.tiered record",
              file=sys.stderr)
        raise SystemExit(1)
    if not ck.get("completed"):
        print("bench gate: 3x-over-budget checkpoint workload did not "
              "complete", file=sys.stderr)
        raise SystemExit(1)
    if ck.get("speedup_vs_restage", 0.0) < CHECKPOINT_MIN_SPEEDUP:
        print(f"bench gate: checkpoint tier "
              f"{ck.get('speedup_vs_restage'):.2f}x vs naive re-staging "
              f"(target {CHECKPOINT_MIN_SPEEDUP}x)", file=sys.stderr)
        raise SystemExit(1)
    ss = rows.get("bench_session.sibling_reads")
    if ss is None:
        print("bench gate: no bench_session.sibling_reads record",
              file=sys.stderr)
        raise SystemExit(1)
    if not ss.get("sibling_reads", 0):
        print("bench gate: interconnect run served zero sibling reads",
              file=sys.stderr)
        raise SystemExit(1)
    if ss.get("speedup_vs_home", 0.0) < SESSION_MIN_SPEEDUP:
        print(f"bench gate: cross-pilot sibling reads only "
              f"{ss.get('speedup_vs_home'):.2f}x vs home re-pull "
              f"(target {SESSION_MIN_SPEEDUP}x)", file=sys.stderr)
        raise SystemExit(1)
    fk = rows.get("bench_session.facade_kmeans")
    if fk is None or not fk.get("completed"):
        print("bench gate: PilotSession façade KMeans did not complete",
              file=sys.stderr)
        raise SystemExit(1)
    # PR 6: the batched task engine must sustain >= 10^5 tiny tasks/s and
    # >= 20x the per-CU submission rate (details in bench_throughput)
    from benchmarks import bench_throughput
    bench_throughput.gate(records)
    # PR 7: chaos-kill one of N pilots mid-KMeans — zero data loss,
    # replication restored, >= 1 respawn, <= 1.5x fault-free wall time
    from benchmarks import bench_resilience
    bench_resilience.gate(records)
    # PR 8: the zero-copy plane — view fetch >= 3x copy fetch on >= 64MiB
    # partitions, steady-state map_reduce no worse than the copy baseline
    from benchmarks import bench_transport
    bench_transport.gate(records)
    # PR 9: LM serving ON the substrate — p99 <= 1.5x the isolated stack
    # at equal batch, exact token accounting, chaos kill loses nothing
    from benchmarks import bench_serving
    bench_serving.gate(records)
    # PR 10: elasticity — burst scale-out >= 1.2x static-small, scale-in
    # drains with zero partition loss, rebalance migrations priced and
    # never sourced from a quarantined pilot
    from benchmarks import bench_autoscale
    bench_autoscale.gate(records)


def main() -> None:
    from benchmarks import (bench_autoscale, bench_checkpoint,
                            bench_fig6_startup, bench_fig7_storage,
                            bench_fig8_profiles, bench_fig9_kmeans,
                            bench_kernels, bench_mapreduce,
                            bench_multipilot, bench_resilience,
                            bench_roofline, bench_serving, bench_session,
                            bench_throughput, bench_tiering,
                            bench_train_step, bench_transport)
    from benchmarks import common
    quick = "--quick" in sys.argv
    json_path = _json_path(sys.argv)
    print("name,us_per_call,derived")
    if quick:
        # CI smoke: the tiering + map_reduce + multipilot + checkpoint +
        # session benches exercise pilots, DUs, the managed hierarchy,
        # eviction policies, the pipelined engine, the distributed
        # Pilot-Data layer, the durable spill/restore path, and the v2
        # façade + cross-pilot interconnect reads end-to-end in seconds
        bench_tiering.run(quick=True)
        bench_mapreduce.run(quick=True)
        bench_multipilot.run(quick=True)
        bench_checkpoint.run(quick=True)
        bench_session.run(quick=True)
        bench_throughput.run(quick=True)
        bench_resilience.run(quick=True)
        bench_transport.run(quick=True)
        bench_serving.run(quick=True)
        bench_autoscale.run(quick=True)
        common.write_json(json_path, meta={"mode": "quick"})
        print(f"# wrote {json_path}", file=sys.stderr)
        _gate(common.records())
        return
    failures = 0
    for mod in (bench_fig6_startup, bench_fig7_storage, bench_fig8_profiles,
                bench_fig9_kmeans, bench_kernels, bench_tiering,
                bench_mapreduce, bench_multipilot, bench_checkpoint,
                bench_session, bench_throughput, bench_resilience,
                bench_transport, bench_serving, bench_autoscale,
                bench_train_step, bench_roofline):
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{mod.__name__},0.0,ERROR", file=sys.stderr)
            traceback.print_exc()
    common.write_json(json_path, meta={"mode": "full"})
    print(f"# wrote {json_path}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
