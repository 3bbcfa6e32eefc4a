"""Median seconds a request waited for a batch row: the ``queued_s`` of
the ``serve.refill`` spans in the window, each its request's admission
less its submission (program spans, ``ServingEngine._serve_loop``)."""
import statistics

import harness

program_spans = harness.sibling("program_spans")


def read(view):
    waits = [s.attrs["queued_s"] for s in program_spans.named(
        program_spans.in_window(view.records), "serve.refill")]
    return statistics.median(waits) if waits else None
