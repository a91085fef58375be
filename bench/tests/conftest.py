"""CPU tests of the benchmark: run with
``JAX_PLATFORMS=cpu python -m pytest bench/tests -q`` from the repository
root (the repository's own test run does not collect them)."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny",
    "source": "a small image dictionary for CPU tests",
    "generator": {"name": "image_mixture",
                  "params": {"n": 64, "p": 512, "rank": 8,
                             "atoms_per_image": 3, "atom_density": 0.4,
                             "spread": 0.2, "noise": 0.1}},
    "session": {"rule": "edpp", "strategy": "fista", "tol": 1e-6,
                "max_iter": 5000, "dtype": "float32",
                "matmul_precision": "highest"},
    "reference": "bench/reference.py",
    "reduced": [], "assumed": {},
}
TINY_MIX = {
    "arrivals": {"kind": "backlog", "count": 400},
    "policy": {"b_max": 4, "deadline_ms": 20, "queue_cap": 16,
               "max_in_flight": 2},
    "grid": {"num_lambdas": 6, "lo_frac": 0.75, "hi_frac": 0.95},
    "warmup": {"batches": 12},
}
TINY_POISSON = dict(TINY_MIX, arrivals={"kind": "poisson", "rate": 4.0})


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout holding the benchmark plus two tiny cells, ``tiny.sat``
    and ``tiny.steady``, added by new files and new BENCHMARK.json entries
    alone. The program's
    plain jnp kernels keep it fast on the CPU."""
    monkeypatch.setenv("REPRO_SCREEN_BACKEND", "jnp")
    monkeypatch.setenv("REPRO_SOLVER_BACKEND", "jnp")
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench" / "mixes" / "tiny-backlog.json").write_text(
        json.dumps(TINY_MIX))
    (root / "bench" / "mixes" / "tiny-poisson.json").write_text(
        json.dumps(TINY_POISSON))
    for cell in ("tiny.sat", "tiny.steady"):
        (root / "bench" / "checks" / f"{cell}.json").write_text(json.dumps(
            {"sample": 8, "limits": {"gap": 2e-5, "lam_err": 1e-5}}))
    spec["configs"].append({"name": "tiny", "source": "tests",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": "tiny.sat", "config": "tiny",
                              "traffic": "tiny-backlog", "chips": 1,
                              "why": "CPU tests"})
    spec["workloads"].append({"name": "tiny.steady", "config": "tiny",
                              "traffic": "tiny-poisson", "chips": 1,
                              "why": "CPU tests"})
    names = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    for name, unit, better in (("latency_p95_ms", "ms", "lower"),
                               ("latency_p50_ms", "ms", "lower"),
                               ("qps", "queries/s", "higher")):
        if name not in names:        # the open-loop metrics, where absent
            spec["end_to_end"].append({
                "name": name, "unit": unit, "better": better, "bound": 0.25,
                "source": "host_clock", "workloads": []})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("qps", "session.compiles.sat", "screen.kept_frac",
                         "solve.iters_per_step", "kernel.screen_roofline",
                         "device.idle_share.sat"):
            m["workloads"].append("tiny.sat")
        if m["name"] in ("latency_p95_ms", "latency_p50_ms"):
            m["workloads"].append("tiny.steady")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
