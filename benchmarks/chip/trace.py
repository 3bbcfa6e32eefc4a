"""Reduction of a profiler trace to device busy time, idle gaps, the op
table and program times.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes into plain
events; ``reduce`` turns those into a ``Summary``.  The two are apart so
that the reduction is checked on a small recorded trace (tests/data).

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation run and ``XLA Modules`` one per program run.  The
host plane's lines are threads, and carry the benchmark's own spans
(``jax.profiler.TraceAnnotation``, named ``bench.*``).  The measured window
is the host span ``bench.window``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"

Event = Tuple[str, float, float]            # name, start_ns, duration_ns


def load_xplane(path: str) -> Dict:
    """Plain events of one trace file: per device plane its ops and
    modules, and every host event with its thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Tuple[str, str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = ("ops" if line.name == OPS_LINE else
                       "modules" if line.name == MODULES_LINE else None)
                if key:
                    d[key].extend((e.name, e.start_ns, e.duration_ns)
                                  for e in line.events)
        elif plane.name == "/host:CPU":
            # the runtime's unnamed line holds most host events (buffer
            # holds, allocations) and says little about a gap
            for line in plane.lines:
                if line.name:
                    host.extend((line.name, e.name, e.start_ns,
                                 e.duration_ns)
                                for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program_name(name: str) -> str:
    """``jit_dec(12)`` -> ``jit_dec``: a program's name without its id."""
    return re.sub(r"\(.*\)$", "", name).strip()


def op_label(name: str) -> str:
    """``%fusion.101 = bf16[..]{..} fusion(...), ...`` -> ``%fusion.101
    fusion``: an op's name and opcode without its operands."""
    if " = " not in name:
        return name
    short, rest = name.split(" = ", 1)
    m = re.search(r"\s([a-z][\w-]*)\(", rest)
    return f"{short} {m.group(1)}" if m else short


def _enclosing(modules: List[Event]):
    """op start -> the program running it, by the modules' intervals."""
    import bisect
    mods = sorted((s, s + d, program_name(n)) for n, s, d in modules)
    starts = [m[0] for m in mods]

    def find(t: float) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t <= mods[i][1] else None
    return find


def self_times(events: List[Event]) -> List[float]:
    """Each op's duration less that of the ops it contains: a while
    loop's event on the ``XLA Ops`` line spans the events of its body."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            if s + d <= events[p][1] + events[p][2]:
                own[p] -= d
        stack.append(i)
    return own


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, w0: float, w1: float):
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def gaps(busy: List[Tuple[float, float]], w0: float, w1: float):
    """The idle intervals of [w0, w1] between merged busy intervals."""
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over the chips
    chips: int
    programs: Dict[str, List[float]]     # name -> each run's seconds, all chips
    ops: Dict[str, float]                # program:op -> self seconds, all chips
    idle: Dict[str, float]               # what the host did -> idle seconds

    def program_seconds(self, prefix: str) -> List[float]:
        """Run times of the programs whose name is `prefix` or starts with
        `prefix` followed by a non-word character."""
        pat = re.compile(re.escape(prefix) + r"(\W|$)")
        return [t for name, ts in self.programs.items()
                if pat.match(name) for t in ts]

    def program_runs(self) -> int:
        return sum(len(ts) for ts in self.programs.values())

    def breakdown(self) -> Dict[str, list]:
        top_ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        top_idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in top_ops],
                "idle_gaps": [[n, s] for n, s in top_idle]}


def _host_labels(host, times: List[float]) -> List[str]:
    """What the host was doing at each of `times` (ascending): the
    innermost benchmark span covering it and, inside that, the innermost
    other host event.  One sweep: events enter a heap by duration as
    their start passes, and leave it once they have ended."""
    import heapq
    evs = sorted((s, d, n) for _, n, s, d in host
                 if n != WINDOW_SPAN and d > 0)
    heaps = {True: [], False: []}
    out, i = [], 0
    for t in times:
        while i < len(evs) and evs[i][0] <= t:
            s, d, n = evs[i]
            heapq.heappush(heaps[n.startswith("bench.")], (d, s + d, n))
            i += 1
        parts = []
        for bench in (True, False):
            h = heaps[bench]
            while h and h[0][1] < t:
                heapq.heappop(h)
            if h:
                parts.append(h[0][2])
        out.append(" / ".join(parts) if parts else "no host span")
    return out


def reduce(events: Dict, chips: int,
           window: Optional[Tuple[float, float]] = None) -> Summary:
    """Busy and idle time, ops and programs inside the measured window,
    which is the host span ``bench.window`` unless given."""
    host = events["host"]
    if window is None:
        spans = [(s, s + d) for _, n, s, d in host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace has no {WINDOW_SPAN} span")
        window = spans[-1]
    w0, w1 = window
    planes = sorted(events["devices"].items(),
                    key=lambda kv: int(DEVICE_PLANE.match(kv[0]).group(1)))
    planes = [(n, d) for n, d in planes if d["ops"]][:chips]
    if not planes:
        raise ValueError("the trace has no device operations")
    busy_total = 0.0
    programs: Dict[str, List[float]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for i, (_, d) in enumerate(planes):
        busy = union(clip([(s, s + dur) for _, s, dur in d["ops"]], w0, w1))
        busy_total += sum(e - s for s, e in busy)
        program_of = _enclosing(d["modules"])
        for (name, s, _), dur in zip(d["ops"], self_times(d["ops"])):
            if w0 <= s < w1:
                prog = program_of(s)
                label = op_label(name)
                ops[f"{prog}:{label}" if prog else label] += dur * 1e-9
        for name, s, dur in d["modules"]:
            if w0 <= s < w1:
                programs[program_name(name)].append(dur * 1e-9)
        if i == 0:   # idle gaps named on the first chip
            idle_spans = gaps(busy, w0, w1)
            labels = _host_labels(host, [(s + e) / 2 for s, e in idle_spans])
            for (s, e), label in zip(idle_spans, labels):
                idle[label] += (e - s) * 1e-9
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total * 1e-9 / len(planes),
                   chips=len(planes), programs=dict(programs),
                   ops=dict(ops), idle=dict(idle))


def reduce_dir(trace_dir: str, chips: int) -> Summary:
    return reduce(load_xplane(find_xplane(trace_dir)), chips)
