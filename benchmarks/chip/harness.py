"""The chip benchmark's harness: finds a cell's pieces by name, checks the
device, runs the cell's driver, reduces the trace and prints the result.

Everything that belongs to one configuration, traffic mix, driver or
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    configs/<config>.json      sizes, source, cut, deployment, driver
    traffic/<mix>.json         parameters for the one generator, traffic.py
    drivers/<driver>.py        one entry point of the system: run(ctx)
    metrics/<metric>.py        read(view) -> number or None

So a later change adds a cell, a mix or a metric with new files and new
entries in ``BENCHMARK.json`` alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
PEAKS_FILE = HERE / "peaks.json"
# the persistent compile cache: a fixed path inside the checkout, so that
# only a checkout's first run of a cell compiles
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class BenchError(RuntimeError):
    """A cell that cannot run as described: missing file, no chip."""


# -- finding the pieces ------------------------------------------------------
def load_module(path: Path, prefix: str):
    """Import one file by path (a metric's name may hold dots), once."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    name = prefix + "_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(path.parents[1])))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def sibling(name: str):
    """One of the benchmark's own modules (``trace``, ``counts``,
    ``traffic``, ``reference/lloyd``...), by path: ``trace`` would else
    meet the standard library's module of that name."""
    return load_module(HERE / f"{name}.py", "chipbench")


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names resolved."""
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    def driver(self):
        return load_module(self.root / "benchmarks" / "chip" / "drivers"
                           / f"{self.config['driver']}.py", "chip_driver")

    def metric_reader(self, name: str) -> Callable:
        mod = load_module(self.root / "benchmarks" / "chip" / "metrics"
                          / f"{name}.py", "chip_metric")
        return mod.read


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_mix(name: str, root: Path = ROOT) -> Dict[str, Any]:
    mix_file = root / "benchmarks" / "chip" / "traffic" / f"{name}.json"
    if not mix_file.is_file():
        raise BenchError(f"traffic file {mix_file} missing")
    return json.loads(mix_file.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name} names unknown config "
                         f"{w['config']!r}")
    cfg_file = root / configs[w["config"]]["file"]
    if not cfg_file.is_file():
        raise BenchError(f"config file {cfg_file} missing")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads(cfg_file.read_text()),
        mix=load_mix(w["traffic"], root),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
        root=root)


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, Any]:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} has no entry in "
                         f"{path.name}; have {sorted(table)}")
    return table[device_kind]


# -- compile accounting ------------------------------------------------------
class CompileClock:
    """Seconds JAX spent compiling and how many programs came from the
    persistent cache, from JAX's own monitoring events.  ``mark()`` starts a
    new span; each span keeps its own counts."""

    def __init__(self):
        import jax
        self.spans: Dict[str, Dict[str, float]] = {}
        self.mark("setup")
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def mark(self, span: str) -> None:
        self.current = self.spans.setdefault(
            span, {"compile_s": 0.0, "compiles": 0, "hits": 0, "lookups": 0})

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.current["compile_s"] += duration
            self.current["compiles"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.current["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.current["lookups"] += 1

    def line(self) -> str:
        return "; ".join(
            f"{name}: {s['compile_s']:.3f} s compiling, {s['compiles']} "
            f"compiles, cache hits {s['hits']} of {s['lookups']} lookups"
            for name, s in self.spans.items())


def use_cache(cache_dir: Path = CACHE_DIR) -> None:
    """Keep every program in the persistent cache inside the checkout.
    The directory is set here even where the environment names another,
    so that two checkouts on one machine share nothing."""
    import jax
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# -- the device --------------------------------------------------------------
def require_chips(chips: int) -> Dict[str, Any]:
    """The accelerator this run measures; raises where JAX finds none or
    fewer chips than the cell asks for.  It never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no accelerator: JAX's first device is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# -- what a driver is handed -------------------------------------------------
@dataclasses.dataclass
class Context:
    """What one run knows.  ``t_start`` is the process start on the
    perf_counter clock; ``trace_dir`` is set for a traced run.  ``control``
    is set only by calibrate.py, which also reads the control's number."""
    cell: Cell
    seed: int
    seconds: float
    clock: Optional[CompileClock]
    t_start: float
    trace_dir: Optional[str] = None
    control: bool = False
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                 flush=True)


@contextlib.contextmanager
def measured_window(ctx: Context):
    """The measured window: the compile clock's ``window`` span, and in a
    traced run the profiler, with the window as the host span
    ``bench.window`` that trace.py reads.  Python tracing stays off."""
    import jax
    if ctx.clock is not None:
        ctx.clock.mark("window")
    if ctx.trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        if ctx.trace_dir:
            jax.profiler.stop_trace()
        if ctx.clock is not None:
            ctx.clock.mark("after")


@dataclasses.dataclass
class View:
    """What a metric reader sees: the driver's records, the reduced trace
    (traced runs only), the chip's peaks and the cell."""
    records: Dict[str, Any]
    trace: Optional[Any]
    peaks: Dict[str, Any]
    cell: Cell


def read_metrics(cell: Cell, specs: List[Dict[str, Any]],
                 view: View) -> Dict[str, Dict[str, Any]]:
    out = {}
    for spec in specs:
        value = cell.metric_reader(spec["name"])(view)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def checks_ok(checks: Dict[str, Dict[str, float]]) -> bool:
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())


def control_checks(checks: Dict[str, Dict[str, float]],
                   control: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The run's checks with the control's readings in place of the
    program's, each against the same limit."""
    return {k: dict(c, value=control.get(k, c["value"]))
            for k, c in checks.items()}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, clock: CompileClock,
        device: Dict[str, Any]) -> Dict[str, Any]:
    """Run one cell and build its result line (a dict)."""
    import tempfile
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    ctx = Context(cell=cell, seed=seed, seconds=seconds, clock=clock,
                  t_start=t_start, trace_dir=trace_dir)
    try:
        records = cell.driver().run(ctx)
        summary = None
        if trace:
            summary = sibling("trace").reduce_dir(trace_dir,
                                                  chips=cell.chips)
    finally:
        if trace_dir:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    # only the per-layer readers need the chip's peaks
    peaks = load_peaks(device["kind"]) if trace else {}
    view = View(records=records, trace=summary, peaks=peaks, cell=cell)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(cell, specs, view)
    if not trace:
        missing = [m["name"] for m in specs if m["name"] not in metrics]
        if missing:
            raise BenchError(f"end-to-end metrics not measured: {missing}")
    dev = dict(device)
    dev["memory_peak_bytes"] = int(records["memory_peak_bytes"])
    out = {"correct": checks_ok(records["checks"]),
           "attempted": int(records["attempted"]),
           "failed": int(records["failed"]),
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = records["checks"]
    return out


def print_result(out: Dict[str, Any]) -> None:
    """Each number compared beside its limit as the last lines on standard
    error, then the result as the last line on standard output.  A number
    that could not be read (no answer to compare) prints as the largest
    float, so that the line stays JSON."""
    for c in out["checks"].values():
        if c["value"] != c["value"] or abs(c["value"]) == float("inf"):
            c["value"] = sys.float_info.max
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(out, allow_nan=False), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        import jax  # noqa: F401 - the first import that touches the chip
        device = require_chips(cell.chips)
        load_peaks(device["kind"])      # an unknown chip is an error
        use_cache()
        clock = CompileClock()
        out = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  clock, device)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"[compile] {clock.line()}", file=sys.stderr, flush=True)
    print_result(out)
    return 0
