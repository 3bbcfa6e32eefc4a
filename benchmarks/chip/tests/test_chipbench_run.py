"""run.py measures only on an accelerator: without one, or without the
program beside it, it exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import CHIP, ROOT


def _run(root, workload="kmeans_i.resident"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"),
         "--workload", workload, "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
    assert '"metrics"' not in proc.stdout


@pytest.mark.parametrize("workload", ["kmeans_i.resident",
                                      "mamba8.chat_closed"])
def test_no_accelerator_no_result(workload):
    proc = _run(ROOT, workload)
    _no_result(proc)
    assert "no accelerator" in proc.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's own files only:
    the program under test is absent."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_unreadable_check_prints_json(capsys):
    from chipbench_tiny import harness
    out = {"correct": harness.checks_ok({"gap": {"value": float("inf"),
                                                 "limit": 0.4}}),
           "attempted": 0, "failed": 0, "metrics": {}, "device": {},
           "checks": {"gap": {"value": float("inf"), "limit": 0.4}}}
    harness.print_result(out)
    std = capsys.readouterr()
    line = json.loads(std.out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["gap"]["value"] > 1e300
    assert std.err.strip().splitlines()[-1].endswith("FAILED")
    assert list(line)[-1] == "checks"
