"""trace.py: the busy union, the idle gaps and what the host did in them,
the op table and program times, on hand-made events and on a small trace
recorded on the chip (tests/data)."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench_tiny import harness

trace = harness.sibling("trace")
DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert busy == [(0, 3), (5, 9), (12, 13)]
    assert trace.gaps(busy, -1, 15) == [(-1, 0), (3, 5), (9, 12), (13, 15)]
    assert trace.clip(busy, 1, 12.5) == [(1, 3), (5, 9), (12, 12.5)]
    assert trace.program_name("jit_dec(123)") == "jit_dec"
    assert trace.program_name("jit_pf") == "jit_pf"
    assert trace.op_label(
        "%fusion.101 = bf16[32,1]{2,0:T(8,128)(2,1)S(1)} fusion(bf16[8] %x),"
        " kind=kOutput") == "%fusion.101 fusion"
    assert trace.op_label(
        "%while.30 = (s32[]{:T(128)}, bf16[1,512]{1,0}) while((s32[]) %t),"
        " condition=%c") == "%while.30 while"


def test_self_times_of_nested_ops():
    ops = [("while", 0, 10), ("body.1", 1, 3), ("body.2", 5, 2),
           ("inner", 5.5, 1), ("after", 12, 2)]
    assert trace.self_times(ops) == [5, 3, 1, 1, 2]


def _events():
    ms = 1e6
    ops0 = [("fusion.1", 1 * ms, 2 * ms), ("fusion.2", 2.5 * ms, 1 * ms),
            ("copy", 3 * ms, 1 * ms), ("fusion.1", 8 * ms, 1 * ms),
            ("outside", 20 * ms, 1 * ms)]
    mods0 = [("jit_dec(7)", 1 * ms, 3 * ms), ("jit_pf(9)", 8 * ms, 1 * ms)]
    ops1 = [("fusion.1", 0 * ms, 5 * ms)]
    mods1 = [("jit_dec(7)", 0 * ms, 5 * ms)]
    host = [("main", "bench.window", 0, 10 * ms),
            ("main", "bench.client_wait", 4 * ms, 3 * ms),
            ("loop", "PjitFunction(dec)", 4.5 * ms, 0.5 * ms),
            ("main", "bench.submit", 9 * ms, 0.5 * ms)]
    return {"devices": {"/device:TPU:1": {"ops": ops1, "modules": mods1},
                        "/device:TPU:0": {"ops": ops0, "modules": mods0}},
            "host": host}


def test_reduce_by_hand():
    s = trace.reduce(_events(), chips=2)
    assert s.window_s == pytest.approx(0.010)
    # chip 0 busy [1,4] and [8,9] = 4 ms; chip 1 [0,5] = 5 ms; mean 4.5 ms
    assert s.busy_s == pytest.approx(0.0045)
    assert s.chips == 2
    assert s.ops == pytest.approx({"jit_dec:fusion.1": 0.007,
                                   "jit_pf:fusion.1": 0.001,
                                   "jit_dec:fusion.2": 0.001,
                                   "jit_dec:copy": 0.001})
    assert sorted(s.program_seconds("jit_dec")) == pytest.approx([0.003,
                                                                   0.005])
    assert s.program_seconds("jit_pf") == pytest.approx([0.001])
    assert s.program_seconds("jit_p") == []
    assert s.program_runs() == 3
    # chip 0 idle: [0,1] (window only), [4,8] (client wait / dispatch at
    # its midpoint 6 ms: client wait only), [9,10] (submit)
    assert s.idle == pytest.approx({"no host span": 0.001,
                                    "bench.client_wait": 0.004,
                                    "bench.submit": 0.001})
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_dec:fusion.1", pytest.approx(0.007)]
    assert len(b["idle_gaps"]) == 3


def test_reduce_one_chip_and_no_window():
    ev = _events()
    s = trace.reduce(ev, chips=1)
    assert s.chips == 1 and s.busy_s == pytest.approx(0.004)
    ev["host"] = [h for h in ev["host"] if h[1] != "bench.window"]
    with pytest.raises(ValueError):
        trace.reduce(ev, chips=1)
    s = trace.reduce(ev, chips=1, window=(0, 30e6))
    assert s.window_s == pytest.approx(0.030)


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json.gz")))
def test_recorded_trace(name):
    """A trace recorded on the chip, cut to a few ms: the reduction agrees
    with a plain sweep over every op, and names the programs it holds."""
    with gzip.open(DATA / name, "rt") as f:
        rec = json.load(f)
    ev = {"devices": {k: {kk: [tuple(e) for e in vv] for kk, vv in v.items()}
                      for k, v in rec["devices"].items()},
          "host": [tuple(h) for h in rec["host"]]}
    s = trace.reduce(ev, chips=rec["chips"])
    (w0, w1), = [(st, st + d) for _, n, st, d in ev["host"]
                 if n == "bench.window"]
    plane = ev["devices"][sorted(ev["devices"])[0]]
    # a plain sweep: sort op edges, count how many ops are running
    edges = sorted([(max(st, w0), 1) for _, st, d in plane["ops"]
                    if st + d > w0 and st < w1]
                   + [(min(st + d, w1), -1) for _, st, d in plane["ops"]
                      if st + d > w0 and st < w1])
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    if rec["chips"] == 1:
        assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert sum(s.idle.values()) == pytest.approx(
        (w1 - w0) * 1e-9 - busy * 1e-9, rel=1e-6)
    # self times add up to the busy time of the ops that start inside
    starts_in = [(st, st + d) for _, st, d in plane["ops"] if w0 <= st < w1]
    assert sum(s.ops.values()) == pytest.approx(
        sum(e - b for b, e in trace.union(starts_in)) * 1e-9, rel=1e-6)
    for program in rec["expect_programs"]:
        assert s.program_seconds(program), program
