"""Milliseconds per Lloyd iteration that ``analytics.kmeans`` spends
bringing the iteration's sums, counts and SSE to the host and updating
the centroids: all ``kmeans.sync`` time over the count of ``kmeans.iter``
spans (program spans)."""
import harness

program_spans = harness.sibling("program_spans")


def read(view):
    spans = program_spans.in_window(view.records)
    iters = len(program_spans.named(spans, "kmeans.iter"))
    sync = sum(s.end_ns - s.start_ns
               for s in program_spans.named(spans, "kmeans.sync"))
    return sync / iters / 1e6 if iters else None
