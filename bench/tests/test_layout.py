"""A cell, a configuration, a traffic mix and a per-layer metric are found
by name, from new files and new BENCHMARK.json entries alone."""

import json
import subprocess
import sys

import numpy as np

from bench import harness, traffic

from conftest import ROOT


def test_new_cell_loads_from_new_files_alone(tiny_root):
    # the fixture copied the benchmark unchanged and only added files
    for path in (ROOT / "bench").rglob("*"):
        if path.is_file() and "tests" not in path.parts \
                and "__pycache__" not in path.parts:
            copy = tiny_root / path.relative_to(ROOT)
            assert copy.read_bytes() == path.read_bytes(), path
    cell = harness.load_cell("tiny.sat", tiny_root)
    assert cell.config["generator"]["name"] == "image_mixture"
    assert cell.mix["grid"]["num_lambdas"] == 6
    assert [m["name"] for m in cell.end_to_end] == ["qps", "setup_s"]
    assert "screen.kept_frac" in [m["name"] for m in cell.per_layer]
    assert hasattr(cell.generator(), "queries")


def test_new_layer_metric_is_read_by_name(tiny_root):
    (tiny_root / "bench" / "layer_metrics" / "tiny.batches.py").write_text(
        "def read(record):\n    return len(record['dispatches'])\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "tiny.batches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serve loop", "moves": "qps",
        "workloads": ["tiny.sat"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("tiny.sat", tiny_root)
    record = {"dispatches": [{"t": 0.5, "steps": []}] * 3, "window": [0.0, 1.0],
              "tickets": [], "config": cell.config, "device": None,
              "compiles_in_window": 0}
    got = harness.layer_metrics(cell, record)
    assert got["tiny.batches"] == {"value": 3, "unit": "count"}
    # readers that find nothing to read leave their metric out
    assert "kernel.screen_roofline" not in got
    assert "screen.kept_frac" not in got


def test_every_committed_cell_loads():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        for m in cell.per_layer:
            assert callable(cell.layer_metric(m["name"]).read)
        offsets, _ = traffic.arrival_offsets(
            cell.mix["arrivals"], spec["run_seconds"],
            np.random.default_rng(0))
        assert len(offsets) > 0


def _run_py(cwd, *args):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os
    env["HOME"] = os.environ.get("HOME", "/tmp")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist.upper.sat",
         "--seed", "3", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_run_exits_nonzero_without_a_chip():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
    assert "no TPU" in out.stderr


def test_run_exits_nonzero_with_the_benchmark_alone(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_py(tmp_path)
    assert out.returncode != 0
    assert not _has_result(out.stdout)
