"""The readers of the program's own spans, on span logs recorded in
traced chip runs (tests/data/spans/<cell>.json.gz): each gives what a
plain loop over the log gives, and None on an empty log or where the
program has no tracing module (the parent of the change that added it)."""
import gzip
import json
import statistics
import sys
from pathlib import Path

import pytest

from chipbench_tiny import harness
from repro.core import tracing

DATA = Path(__file__).resolve().parent / "data" / "spans"
READERS = {"kmeans_i.resident": ["kmeans_sync_ms_per_iter",
                                 "mapreduce_dispatch_ms"],
           "mamba8.chat_closed": ["serve_queue_wait_s",
                                  "serve_host_ms_per_step"]}
CASES = [(cell, m) for cell, ms in READERS.items() for m in ms]


@pytest.fixture
def log():
    tracing.spans().clear()
    yield tracing.spans()
    tracing.spans().clear()


def _recorded(cell):
    with gzip.open(DATA / f"{cell}.json.gz", "rt") as f:
        rec = json.load(f)
    return rec["records"], [tracing.Span(*s) for s in rec["spans"]]


def _read(cell, metric, records):
    c = harness.load_cell(cell)
    view = harness.View(records=records, trace=None, peaks={}, cell=c)
    return c.metric_reader(metric)(view)


def _plain(metric, records, spans):
    """Each metric as a plain loop over the log."""
    if "window" in records:
        t0, t1 = records["window"]
        spans = [s for s in spans
                 if t0 * 1e9 <= s.start_ns and s.end_ns <= t1 * 1e9]
    values = []
    if metric == "serve_queue_wait_s":
        for s in spans:
            if s.name == "serve.refill":
                values.append(s.attrs["queued_s"])
        return statistics.median(values)
    if metric == "serve_host_ms_per_step":
        for step in spans:
            if step.name == "serve.step":
                host = step.end_ns - step.start_ns
                for s in spans:
                    if s.name == "serve.sync" and s.parent == step.id:
                        host -= s.end_ns - s.start_ns
                values.append(host / 1e6)
        return statistics.median(values)
    if metric == "kmeans_sync_ms_per_iter":
        sync, iters = 0, 0
        for s in spans:
            if s.name == "kmeans.sync":
                sync += s.end_ns - s.start_ns
            if s.name == "kmeans.iter":
                iters += 1
        return sync / iters / 1e6
    assert metric == "mapreduce_dispatch_ms"
    for job in spans:
        if job.name == "mapreduce.job":
            folds = [s.start_ns for s in spans if s.name == "mapreduce.fold"
                     and s.attrs["job"] == job.attrs["job"]]
            if folds:
                values.append((min(folds) - job.start_ns) / 1e6)
    return statistics.median(values)


@pytest.mark.parametrize("cell,metric", CASES)
def test_reader_matches_a_plain_loop(log, cell, metric):
    records, spans = _recorded(cell)
    log.extend(spans)
    got = _read(cell, metric, records)
    assert got is not None and got > 0
    assert got == pytest.approx(_plain(metric, records, spans), rel=1e-12)


def test_recorded_logs_hold_what_the_readers_need():
    records, spans = _recorded("mamba8.chat_closed")
    t0, t1 = records["window"]
    names = {s.name for s in spans}
    assert {"serve.step", "serve.sync", "serve.refill", "serve.account",
            "serve.decode"} <= names
    # spans outside the window, so the window is exercised
    assert any(s.start_ns < t0 * 1e9 for s in spans)
    assert any(s.end_ns > t1 * 1e9 for s in spans)
    _, spans = _recorded("kmeans_i.resident")
    assert {"kmeans.iter", "kmeans.sync", "mapreduce.job", "mapreduce.fold",
            "mapreduce.map", "mapreduce.combine"} <= {s.name for s in spans}
    assert len({s.thread for s in spans}) >= 2    # the fold on a worker


@pytest.mark.parametrize("cell,metric", CASES)
def test_reader_none_without_spans(log, monkeypatch, cell, metric):
    records, spans = _recorded(cell)
    assert _read(cell, metric, records) is None
    log.extend(spans)
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read(cell, metric, records) is None
