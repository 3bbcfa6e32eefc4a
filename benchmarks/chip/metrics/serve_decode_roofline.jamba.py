"""The Jamba decode step's share of its roofline, in percent: the least
time one step of the engine's whole batch could take (counts_jamba.py:
bytes-bound; the weights with every held expert, K/V at the rows' mean
fill over the window's completed requests, the SSM state read and written,
the logits) over the mean device time of the decode program (trace), found
by the name jit gives it, ``jit_dec`` (ServingEngine._pilot_runtime)."""
import harness

counts_jamba = harness.sibling("counts_jamba")
tokens = harness.load_module(harness.HERE / "metrics" / "serve_tokens_per_s.py",
                             "chip_metric")
PROGRAM = "jit_dec"


def read(view):
    if view.trace is None:
        return None
    runs = view.trace.program_seconds(PROGRAM)
    done = tokens.completed(view.records)
    if not runs or not done:
        return None
    c = view.cell.config
    fill = counts_jamba.decode_fill(done)
    least, _ = counts_jamba.least_time(
        *counts_jamba.decode_step(c, c["batch_size"], fill), view.peaks)
    return 100.0 * least / (sum(runs) / len(runs))
