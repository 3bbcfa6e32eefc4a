"""The rate sweep that fixed the open-loop mix's rate, run once on the chip:

    python benchmarks/chip/sweep.py --workload mamba8.chat_closed \
        --traffic chat_open --rates 3,4,5,6 --seconds 20 --seed 77

Each rate is one whole run of the cell's configuration under the open
mix ``--traffic``, with the mix's ``rate_per_s`` replaced.  It prints, per rate, the requests due and finished by the
window's close, and the median and 95th-percentile latency of the first
and second half of the window: a backlog that grows shows as a second
half slower than the first.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def halves(records) -> dict:
    t0, t1 = records["window"]
    mid = (t0 + t1) / 2
    out = {}
    for name, lo, hi in (("first", t0, mid), ("second", mid, t1)):
        lat = sorted(q["t_done"] - q["due"] if q["ok"] else math.inf
                     for q in records["requests"] if lo <= q["due"] < hi)
        if lat:
            out[name] = {"n": len(lat), "p50": statistics.median(lat),
                         "p95": lat[math.ceil(0.95 * len(lat)) - 1]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    cell.mix = harness.load_mix(args.traffic)
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.require_chips(cell.chips)
    harness.use_cache()
    clock = harness.CompileClock()
    driver = cell.driver()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["rate_per_s"] = rate
        t0 = time.perf_counter()
        rec = driver.run(harness.Context(cell=cell, seed=args.seed,
                                         seconds=args.seconds, clock=clock,
                                         t_start=t0))
        t_end = rec["window"][1]
        due = [q for q in rec["requests"] if q["due"] < t_end]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "finished_by_close": sum(1 for q in due if q["ok"]
                                     and q["t_done"] <= t_end),
            "failed": rec["failed"], "halves": halves(rec),
            "generator_late_s": rec["generator_late_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
