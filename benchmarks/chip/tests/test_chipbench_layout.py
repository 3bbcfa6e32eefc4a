"""BENCHMARK.json and the files it names: every piece is found by its
name, the file keeps to the benchmark's contract, and a configuration, a
traffic mix and a metric are added with new files and entries alone."""
import hashlib
import json
import re
import shutil

import pytest

from chipbench_tiny import CHIP, ROOT, harness

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_every_piece(cell):
    c = harness.load_cell(cell)
    assert c.driver().run
    for m in c.end_to_end + c.per_layer:
        assert callable(c.metric_reader(m["name"]))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (CHIP / "metrics" / f"{metric['name']}.py").is_file()
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        for cell in metric["workloads"]:
            assert harness.applies(moved, cell), (metric["name"], cell)
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_cells_and_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)


def _tree_digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_additions_need_only_new_files(tmp_path):
    """A new configuration, traffic mix and per-layer metric in a copy of
    the benchmark: new files and new entries in BENCHMARK.json, and no
    other file touched."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = _tree_digest(chip)
    cfg = json.loads((chip / "configs" / "kmeans_paper_i.json").read_text())
    cfg.update(name="kmeans_paper_ii", points=100_000, clusters=500)
    (chip / "configs" / "kmeans_paper_ii.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "resident_jobs_3.json").write_text(json.dumps(
        {"iters_per_job": 3}))
    (chip / "metrics" / "kmeans_jobs.new.py").write_text(
        "def read(view):\n    return view.records['jobs']\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "kmeans_paper_ii",
                             "source": "https://arxiv.org/abs/1501.05041",
                             "file": "benchmarks/chip/configs/"
                                     "kmeans_paper_ii.json",
                             "reduced": [], "why": "scenario (ii)"})
    bench["workloads"].append({"name": "kmeans_ii.resident",
                               "config": "kmeans_paper_ii",
                               "traffic": "resident_jobs_3", "chips": 1,
                               "why": "scenario (ii), 3 iterations a job"})
    bench["end_to_end"][0]["workloads"].append("kmeans_ii.resident")
    bench["per_layer"].append({"name": "kmeans_jobs.new", "unit": "jobs",
                               "better": "higher", "source": "host_clock",
                               "layer": "map_reduce fold and task engine",
                               "moves": "kmeans_iter_s",
                               "workloads": ["kmeans_ii.resident"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("kmeans_ii.resident", root=tmp_path)
    assert cell.config["clusters"] == 500
    assert cell.mix["iters_per_job"] == 3
    assert [m["name"] for m in cell.per_layer] == ["kmeans_jobs.new"]
    view = harness.View(records={"jobs": 7}, trace=None, peaks={}, cell=cell)
    got = harness.read_metrics(cell, cell.per_layer, view)
    assert got == {"kmeans_jobs.new": {"value": 7.0, "unit": "jobs"}}
    assert cell.driver().run
    after = _tree_digest(chip)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/kmeans_paper_ii.json", "traffic/resident_jobs_3.json",
        "metrics/kmeans_jobs.new.py"}


def test_missing_pieces_are_errors(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    with pytest.raises(harness.BenchError):
        harness.load_cell("kmeans_i.resident", root=tmp_path)
    with pytest.raises(harness.BenchError):
        harness.load_cell("no_such_cell")


def test_peaks_table():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")
