"""repro.core.tracing: spans recorded only while a profiler session runs,
nested per thread, on the profiler's trace with their attrs; and the
spans of the serving loop and of KMeans over map_reduce."""
import glob
import threading

import jax
import numpy as np
import pytest

from repro.core import PilotSession, tracing
from repro.core.tracing import span, spans
from repro.serving import ServingEngine
from test_serving import _StubModel


@pytest.fixture
def profiler(tmp_path):
    """A profiler session for the test's body; the span log starts and
    ends empty."""
    spans().clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()
        spans().clear()


def _named(name):
    return [s for s in spans() if s.name == name]


def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    made = []

    class Annotation:
        def __init__(self, *a, **kw):
            made.append(a)

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    spans().clear()
    first = span("a", rows=3)
    with first, span("b"):
        pass
    assert first is span("c") is tracing._NULL
    assert made == [] and len(spans()) == 0


def test_gate_follows_start_and_stop(tmp_path):
    spans().clear()
    assert span("before") is tracing._NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert span("during") is not tracing._NULL
        with span("during"):
            pass
    finally:
        jax.profiler.stop_trace()
    with span("after"):
        pass
    assert [s.name for s in spans()] == ["during"]
    spans().clear()


def test_nesting_per_thread(profiler):
    both_inside = threading.Barrier(2, timeout=10)

    def work(tag):
        with span("outer", tag=tag):
            with span("inner", tag=tag):
                both_inside.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    outer = {s.attrs["tag"]: s for s in _named("outer")}
    inner = {s.attrs["tag"]: s for s in _named("inner")}
    assert set(outer) == set(inner) == {"a", "b"}
    for tag in "ab":
        assert inner[tag].parent == outer[tag].id
        assert inner[tag].thread == outer[tag].thread
        assert outer[tag].parent is None
        assert (outer[tag].start_ns <= inner[tag].start_ns
                <= inner[tag].end_ns <= outer[tag].end_ns)
    assert outer["a"].thread != outer["b"].thread


def test_spans_reach_the_xplane_with_attrs(tmp_path):
    spans().clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("serve.refill", rid=7, queued_s=1.25):
            with span("serve.sync"):
                jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    spans().clear()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    found[e.name] = dict(e.stats)
    assert found == {"serve.refill": {"rid": 7, "queued_s": 1.25},
                     "serve.sync": {}}


def test_serving_engine_spans_and_request_times(profiler):
    """One request opens the engine (its first fill, a wave of one); the
    rest enter freed rows one refill each."""
    rng = np.random.default_rng(0)
    with PilotSession() as s:
        s.add_pilots(1, memory_gb=0.25)
        with ServingEngine(s, _StubModel(), batch_size=2, max_len=32,
                           page_tokens=4) as eng:
            eng.deploy()
            reqs = [eng.submit(rng.integers(0, 32, size=5), 3)]
            eng.drain(timeout=60)
            reqs += [eng.submit(rng.integers(0, 32, size=4 + i % 3), 3 + i)
                     for i in range(5)]
            eng.drain(timeout=60)
            st = eng.stats()
    refills = _named("serve.refill")
    assert sorted(s.attrs["rid"] for s in refills) == sorted(
        r.rid for r in reqs)
    assert [s.attrs.get("rows") for s in refills].count(1) == 1
    by_rid = {s.attrs["rid"]: s for s in refills}
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
        assert by_rid[r.rid].attrs["queued_s"] == r.queue_wait_s
    steps = {s.id: s for s in _named("serve.step")}
    for child in ("serve.sync", "serve.account"):
        assert sorted(s.parent for s in _named(child)) == sorted(steps)
    decodes = _named("serve.decode")
    assert {s.parent for s in decodes} <= set(steps)
    assert len(decodes) == st["decode_steps"]
    assert sum(s.attrs["rows"] for s in steps.values()) == (
        st["tokens_served"]) == sum(len(r.result()) for r in reqs)
    for name in ("queue_wait_s", "ttft_s", "latency_s"):
        assert 0 < st[f"p50_{name}"] <= st[f"p99_{name}"]


def test_kmeans_spans_per_iteration_and_job(profiler):
    pts = np.random.default_rng(1).normal(size=(800, 4)).astype(np.float32)
    with PilotSession() as s:
        (p,) = s.add_pilots(1, memory_gb=0.25)
        du = s.data("points", pts, parts=4)
        du.replicate_to_pilot(p, parts=[0, 1, 2, 3], tier="device")
        res = s.kmeans(du, k=3, iters=3)
    assert len(res.sse_history) == 3
    iters = _named("kmeans.iter")
    assert sorted(s.attrs["iter"] for s in iters) == [0, 1, 2]
    syncs = _named("kmeans.sync")
    assert sorted(s.parent for s in syncs) == sorted(s.id for s in iters)
    jobs = {s.attrs["job"]: s for s in _named("mapreduce.job")}
    assert len(jobs) == 3
    assert {s.parent for s in jobs.values()} == {s.id for s in iters}
    folds = _named("mapreduce.fold")
    assert sorted(s.attrs["job"] for s in folds) == sorted(jobs)
    for f in folds:
        assert f.attrs["parts"] == 4
        assert f.start_ns >= jobs[f.attrs["job"]].start_ns
    maps = _named("mapreduce.map")
    assert len(maps) == 12
    assert {m.parent for m in maps} == {f.id for f in folds}
