"""A configuration's file places the dictionary on a mesh, sets its groups
and names its reference, and a cell that uses them is added by new files
alone: the harness reads ``session.mesh``, ``session.groups`` and
``reference`` and passes them on."""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from bench import calibrate, harness

from conftest import ROOT, TINY_CONFIG, TINY_MIX

MESH_4X1 = {"axes": ["query", "feature"], "shape": [4, 1]}

# a reference of the tiny root's own: it records each call beside itself
STUB_REFERENCE = '''
import json
from pathlib import Path

import numpy as np

CALLS = Path(__file__).with_suffix(".calls.jsonl")


def _record(kind, n, session):
    with CALLS.open("a") as f:
        f.write(json.dumps({"kind": kind, "answers": n,
                            "session": session}) + "\\n")


def certify(X64, ys, answers, grid, session):
    _record("certify", len(answers), session)
    return {"lam_err": 0.0, "gap": 0.0}


def reference_path(X, Y, grid, *, precision, tol, max_iter):
    _record("reference_path", len(Y), None)
    B, p, K = len(Y), X.shape[1], grid["num_lambdas"]
    return (np.ones((B, K)), np.zeros((B, K, p)), np.zeros((B, K, p), bool),
            np.ones((B,), bool))
'''


def add_cell(root, name, config, mix, chips=1, files=None):
    """A new configuration, mix, check and cell in the checkout at
    ``root``, by new files and new BENCHMARK.json entries alone."""
    bench = root / "bench"
    for rel, text in (files or {}).items():
        (root / rel).write_text(text)
    (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
    (bench / "mixes" / f"{name}-mix.json").write_text(json.dumps(mix))
    (bench / "checks" / f"{name}.cell.json").write_text(json.dumps(
        {"sample": 8, "limits": {"gap": 2e-5, "lam_err": 1e-5}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "tests",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": f"{name}.cell", "config": name,
                              "traffic": f"{name}-mix", "chips": chips,
                              "why": "CPU tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("qps", "screen.kept_frac"):
            m["workloads"].append(f"{name}.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.load_cell(f"{name}.cell", root)


def _config(**session):
    return dict(TINY_CONFIG, session=dict(TINY_CONFIG["session"], **session))


def _fitted(monkeypatch):
    """Every session LassoSession.fit returns, with the keywords it got."""
    from repro.core import session as sess_mod
    real = sess_mod.LassoSession.fit.__func__
    fitted = []

    def fit(cls, X, **kw):
        fitted.append((real(cls, X, **kw), kw))
        return fitted[-1][0]

    monkeypatch.setattr(sess_mod.LassoSession, "fit", classmethod(fit))
    return fitted


def test_a_configuration_without_placement_is_fitted_as_before(tiny_root):
    cell = harness.load_cell("tiny.sat", tiny_root)
    assert harness.fit_options(cell.config["session"], ["cpu:0"]) == {}


def test_mesh_not_of_the_cells_chips_is_refused_before_data(
        tiny_root, monkeypatch):
    cell = add_cell(tiny_root, "tinymesh", _config(mesh=MESH_4X1), TINY_MIX,
                    chips=1)

    def make_data(*a, **k):
        raise AssertionError("data made before the mesh was checked")

    monkeypatch.setattr(harness, "make_data", make_data)
    with pytest.raises(ValueError, match=r"mesh \(4, 1\) holds 4 devices"):
        harness.run_cell(cell, 3, 2.0, False, t_process=time.perf_counter(),
                         require_tpu=False)


def test_groups_and_own_reference_reach_fit_and_check(tiny_root,
                                                      monkeypatch):
    stub = "bench/group_stub_reference.py"
    config = dict(_config(groups=4), reference=stub)
    cell = add_cell(tiny_root, "tinygroup", config, TINY_MIX,
                    files={stub: STUB_REFERENCE})
    fitted = _fitted(monkeypatch)
    out = harness.run_cell(cell, 21, 3.0, False,
                           t_process=time.perf_counter(), require_tpu=False)
    (session, kw), = fitted
    assert kw["groups"] == 4 and "mesh" not in kw
    assert session.groups == 4
    calls = [json.loads(line) for line in
             (tiny_root / stub).with_suffix(".calls.jsonl").read_text()
             .splitlines()]
    assert calls == [{"kind": "certify", "answers": 8,
                      "session": config["session"]}]
    assert harness.is_correct(out["check"]), out["check"]
    assert out["failed"] == 0


def test_control_calls_the_configurations_reference(tiny_root):
    stub = "bench/stub_reference.py"
    cell = add_cell(tiny_root, "tinystub",
                    dict(TINY_CONFIG, reference=stub), TINY_MIX,
                    files={stub: STUB_REFERENCE})
    got = calibrate.control(cell, 5, "high")
    assert got["check"] == {"lam_err": 0.0, "gap": 0.0}
    calls = [json.loads(line)["kind"] for line in
             (tiny_root / stub).with_suffix(".calls.jsonl").read_text()
             .splitlines()]
    # 8 sampled queries in batches of b_max 4
    assert calls == ["reference_path", "reference_path", "certify"]


MESH_RUN = textwrap.dedent('''
    import json, sys, time
    from pathlib import Path
    root = Path(sys.argv[1])
    sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src"]
    from bench import harness
    from repro.core import session as sess_mod
    real = sess_mod.LassoSession.fit.__func__
    meshes = []

    def fit(cls, X, **kw):
        s = real(cls, X, **kw)
        meshes.append(dict(s.mesh.shape))
        return s

    sess_mod.LassoSession.fit = classmethod(fit)
    cell = harness.load_cell("tinyq4.cell", root)
    out = harness.run_cell(cell, 31, 4.0, False,
                           t_process=time.perf_counter(), require_tpu=False)
    print(json.dumps({"correct": harness.is_correct(out["check"]),
                      "check": out["check"], "failed": out["failed"],
                      "count": out["device"]["count"], "meshes": meshes}))
''')


def test_mesh_cell_runs_on_four_devices(tiny_root):
    add_cell(tiny_root, "tinyq4", _config(mesh=MESH_4X1),
             dict(TINY_MIX, policy=dict(TINY_MIX["policy"], b_max=8)),
             chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", MESH_RUN, str(tiny_root), str(ROOT)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.splitlines()[-1])
    assert got["correct"], got["check"]
    assert got["failed"] == 0 and got["count"] == 4
    assert got["meshes"] == [{"query": 4, "feature": 1}]


def test_staged_four_chip_cell_loads_from_entries_alone(tiny_root):
    """``mnist.q4`` and its mix wait for the program's mesh path to stop
    compiling on every call (PERF.md, Open questions): the cell needs only
    its BENCHMARK.json entries and a check file."""
    (tiny_root / "bench" / "checks" / "mnist.q4.sat.json").write_text(
        json.dumps({"sample": 16, "limits": {"gap": 2e-5, "lam_err": 1e-5}}))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "mnist.q4", "source": "tests",
                            "file": "bench/configs/mnist.q4.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": "mnist.q4.sat", "config": "mnist.q4",
                              "traffic": "image-backlog-upper-q4",
                              "chips": 4, "why": "CPU tests"})
    spec["per_layer"].append({
        "name": "device.collective_ms_per_step", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "mesh",
        "moves": "qps", "workloads": ["mnist.q4.sat"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("mnist.q4.sat", tiny_root)
    one = harness.load_cell("mnist.upper.sat", tiny_root)
    session = dict(cell.config["session"])
    assert session.pop("mesh") == MESH_4X1
    assert session == one.config["session"]
    assert cell.config["generator"] == one.config["generator"]
    assert cell.config["reference"] == one.config["reference"]
    assert cell.mix["policy"]["b_max"] == 4 * one.mix["policy"]["b_max"]
    assert cell.mix["grid"] == one.mix["grid"]
    assert callable(cell.layer_metric("device.collective_ms_per_step").read)
    with pytest.raises(ValueError, match="the cell has 1 chips"):
        harness.fit_options(cell.config["session"], ["cpu:0"])
