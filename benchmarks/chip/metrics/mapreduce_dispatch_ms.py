"""Median milliseconds from a ``map_reduce`` call to the start of its
first fold, matched by the ``job`` attr: grouping, Compute-Unit
submission and the pilot's pickup (program spans, ``core/mapreduce.py``)."""
import statistics

import harness

program_spans = harness.sibling("program_spans")


def read(view):
    spans = program_spans.in_window(view.records)
    fold = {}
    for s in program_spans.named(spans, "mapreduce.fold"):
        job = s.attrs["job"]
        fold[job] = min(fold.get(job, s.start_ns), s.start_ns)
    waits = [fold[s.attrs["job"]] - s.start_ns
             for s in program_spans.named(spans, "mapreduce.job")
             if s.attrs["job"] in fold]
    return statistics.median(waits) / 1e6 if waits else None
