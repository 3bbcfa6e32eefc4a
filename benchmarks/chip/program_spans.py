"""The program's own spans (``repro.core.tracing``), for the per-layer
metrics that read them.  The program records spans only while a profiler
session runs, so in a traced run the log covers the traced period; where
the records give the window (``records["window"]``, perf_counter
seconds), only spans that start and end inside it are kept.  A program
without the module, or a run that recorded nothing, gives an empty list,
and the metric reads None."""
from typing import Dict, List


def in_window(records: Dict) -> List:
    try:
        from repro.core import tracing
    except ImportError:
        return []
    log = list(tracing.spans())
    if "window" not in records:
        return log
    t0, t1 = (int(t * 1e9) for t in records["window"])
    return [s for s in log if t0 <= s.start_ns and s.end_ns <= t1]


def named(spans: List, name: str) -> List:
    return [s for s in spans if s.name == name]
