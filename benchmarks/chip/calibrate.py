"""Readings that the limits of ``correct`` are set from, run on the chip:

    python benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,3 --seconds <s> [--control]

For each seed, in one process, one whole run of the cell (set-up, a
window at the cell's own load, the comparison with the reference) and,
with ``--control``, the control's reading beside it: the reference
computed in the precision below the configuration's, compared in the
same way.  One JSON line per seed on standard output, with ``correct`` as
the run decides it and, with ``--control``, ``control_correct``: the same
decision (``harness.checks_ok``) with the control's readings in place of
the program's.  The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    device = harness.require_chips(cell.chips)
    harness.use_cache()
    clock = harness.CompileClock()
    driver = cell.driver()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        clock.mark(f"seed {seed}")
        ctx = harness.Context(cell=cell, seed=seed, seconds=args.seconds,
                              clock=clock, t_start=t0, control=args.control)
        rec = driver.run(ctx)
        control = rec.get("control")
        row = {
            "workload": cell.name, "seed": seed, "device": device["kind"],
            "readings": {k: v["value"] for k, v in rec["checks"].items()},
            "limits": {k: v["limit"] for k, v in rec["checks"].items()},
            "correct": harness.checks_ok(rec["checks"]),
            "control": control, "attempted": rec["attempted"],
            "failed": rec["failed"], "run_s": time.perf_counter() - t0}
        if control is not None:
            row["control_correct"] = harness.checks_ok(
                harness.control_checks(rec["checks"], control))
        print(json.dumps(row), flush=True)
        del rec
        gc.collect()
    print(f"[compile] {clock.line()}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
