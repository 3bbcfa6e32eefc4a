"""The decode step's share of its roofline, in percent: the least time one
step of the engine's whole batch could take (counts.py: bytes-bound, the
weights and the SSM state) over the mean device time of the decode
program (trace).  Decode is found by the name jit gives it today,
``jit_dec`` (ServingEngine._pilot_runtime)."""
import harness

counts = harness.sibling("counts")
PROGRAM = "jit_dec"


def read(view):
    if view.trace is None:
        return None
    runs = view.trace.program_seconds(PROGRAM)
    if not runs:
        return None
    c = view.cell.config
    least, _ = counts.least_time(
        *counts.mamba_decode_step(c, c["batch_size"]), view.peaks)
    return 100.0 * least / (sum(runs) / len(runs))
