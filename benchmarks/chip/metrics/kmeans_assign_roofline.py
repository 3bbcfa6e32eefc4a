"""The assignment's share of its roofline, in percent: the least time one
iteration's assignment over all points could take on the chip (counts.py,
bytes-bound: the float32 points read once) over the device time of the
assignment program per iteration (trace).  The program is found by the
name jit gives it today, ``jit_assign_partial``."""
import harness

counts = harness.sibling("counts")
PROGRAM = "jit_assign_partial"


def read(view):
    if view.trace is None or not view.records["iterations"]:
        return None
    runs = view.trace.program_seconds(PROGRAM)
    if not runs:
        return None
    c = view.cell.config
    least, _ = counts.least_time(
        *counts.kmeans_assign(c["points"], c["clusters"], c["dim"]),
        view.peaks)
    return 100.0 * least / (sum(runs) / view.records["iterations"])
