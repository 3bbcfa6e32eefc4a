"""Median host milliseconds of a decode-loop step outside its one wait on
the device: each ``serve.step`` span's duration less that of its
``serve.sync`` child (program spans, ``ServingEngine._serve_loop``)."""
import statistics
from collections import defaultdict

import harness

program_spans = harness.sibling("program_spans")


def read(view):
    spans = program_spans.in_window(view.records)
    sync = defaultdict(int)
    for s in program_spans.named(spans, "serve.sync"):
        sync[s.parent] += s.end_ns - s.start_ns
    host = [s.end_ns - s.start_ns - sync[s.id]
            for s in program_spans.named(spans, "serve.step")]
    return statistics.median(host) / 1e6 if host else None
