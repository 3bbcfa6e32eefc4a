"""Shared by the benchmark's CPU tests: the harness on the path, and the
cells cut to sizes a CPU test run holds (the limits stay the config's)."""
import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny_cell(name: str, traffic: str = None, **config):
    """`name` cut to test size; `traffic` swaps in another mix by name."""
    cell = harness.load_cell(name)
    if traffic is not None:
        cell.mix = harness.load_mix(traffic)
    if cell.config["driver"] == "kmeans_session":
        cell.config.update(points=20000, clusters=10)
    else:
        cell.config.update(hidden_size=64, intermediate_size=128,
                           time_step_rank=4, vocab_size=256,
                           num_hidden_layers=2, batch_size=4,
                           check_tokens=200, check_requests=8)
        cell.mix.update(prompt={"median": 12, "sigma": 0.5, "min": 8,
                                "max": 16},
                        output={"median": 8, "sigma": 0.5, "min": 4,
                                "max": 24}, block=4)
        if cell.mix["loop"] == "closed":
            cell.mix["clients"] = 8
        else:
            cell.mix["rate_per_s"] = 20.0
    cell.config.update(config)
    return cell


def run_tiny(cell, seed: int = 3, seconds: float = 1.5, control=False):
    """One whole run of `cell` on the CPU, the chip check skipped: the
    result line, as harness.run builds it."""
    clock = harness.CompileClock()
    if not control:
        return harness.run(cell, seed, seconds, False, time.perf_counter(),
                           clock, CPU)
    ctx = harness.Context(cell=cell, seed=seed, seconds=seconds, clock=clock,
                          t_start=time.perf_counter(), control=True)
    return cell.driver().run(ctx)
