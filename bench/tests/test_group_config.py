"""The group-Lasso configuration ``gsynth.m10`` and its cell
``gsynth.m10.sat`` on the CPU: the generator, the plain reference's
``certify`` (a right answer passes, planted faults do not), the three
readers the cell adds, and a small run of the cell through the harness."""

import json
import shutil
import time

import jax
import numpy as np
import pytest

from bench import calibrate, harness, reference_group

from conftest import ROOT

GEN = harness.BENCH / "generators" / "group_gaussian.py"
CELL = "gsynth.m10.sat"
LIMITS = json.loads((ROOT / "bench" / "checks" / f"{CELL}.json")
                    .read_text())["limits"]
SMALL = {"n": 60, "p": 400, "groups": 10, "active_groups": 4, "sigma": 0.1}
GRID = {"num_lambdas": 6, "lo_frac": 0.5, "hi_frac": 0.95}


def _data(seed, count, params=SMALL):
    gen = harness.load_module(GEN)
    X, state = gen.dictionary(jax.random.key(seed), params)
    Y = gen.queries(jax.random.key(seed + 1), params, X, state, count)
    return X, Y


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

def test_generator_shapes_dtype_and_seeding():
    X, Y = _data(0, 256)
    assert X.shape == (60, 400) and Y.shape == (256, 60)
    assert X.dtype == np.float32 and Y.dtype == np.float32
    X2, Y2 = _data(0, 256)
    np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
    np.testing.assert_array_equal(np.asarray(Y), np.asarray(Y2))
    X3, Y3 = _data(5, 256)
    assert not np.array_equal(np.asarray(X), np.asarray(X3))
    assert not np.array_equal(np.asarray(Y), np.asarray(Y3))
    X = np.asarray(X, np.float64)
    assert abs(X.mean()) < 0.02 and abs(X.var() - 1.0) < 0.03
    # y = X beta + sigma eps with 4 groups of 10 coefficients U(-1, 1):
    # E[y_i^2] = 40 / 3 + sigma^2
    want = 4 * 10 / 3 + 0.01
    assert abs(np.mean(np.asarray(Y, np.float64) ** 2) / want - 1) < 0.1


# ---------------------------------------------------------------------------
# certify: a right answer passes, planted faults are refused
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def answered():
    """Two queries answered by the plain reference at full precision."""
    X, Y = _data(3, 2)
    lams, betas, masks, conv = reference_group.reference_path(
        X, Y, GRID, precision="highest", tol=1e-7, max_iter=20000, m=10)
    assert conv.all()
    answers = [(lams[q], betas[q].copy(), masks[q].copy()) for q in range(2)]
    return np.asarray(X, np.float64), np.asarray(Y), answers


def _certify(answered, answers):
    X64, Y, _ = answered
    return reference_group.certify(X64, Y, answers, GRID, {"groups": 10})


def _refused(worst):
    return any(worst[k] > LIMITS[k] for k in LIMITS)


def test_a_right_answer_passes(answered):
    worst = _certify(answered, answered[2])
    assert worst["gap"] <= LIMITS["gap"] / 3, worst
    assert worst["lam_err"] <= LIMITS["lam_err"] / 3, worst


def _altered_beta(answers):
    lams, betas, masks = answers[0]
    betas[-1] *= 1.05


def _discarded_active_group(answers):
    lams, betas, masks = answers[0]
    active = np.flatnonzero(np.abs(betas[-1]).reshape(-1, 10).sum(axis=1))
    masks[-1, active[0]] = True


def _grid_off_by_one_step(answers):
    lams, betas, masks = answers[0]
    step = lams[0] - lams[1]
    answers[0] = (np.r_[lams[1:], lams[-1] - step], betas, masks)


@pytest.mark.parametrize("fault", [_altered_beta, _discarded_active_group,
                                   _grid_off_by_one_step])
def test_planted_faults_are_refused(answered, fault):
    answers = [(lams.copy(), betas.copy(), masks.copy())
               for lams, betas, masks in answered[2]]
    fault(answers)
    worst = _certify(answered, answers)
    assert _refused(worst), worst


def test_reference_takes_the_configurations_group_size():
    assert reference_group.configured_groups() == 10


# ---------------------------------------------------------------------------
# the readers the cell adds
# ---------------------------------------------------------------------------

N, P, M, HBM = 250, 200000, 10, 819e9


def _screen(t0, kernel_us, reduce_us, b=8, other=None):
    """One group screen's device operations as a v5e trace names them: X
    padded, the kernel, then the reduction's chain (slice and square, the
    reshape to (B, G, m), the sum, the square root); then an operation of
    the next program, which ends the chain."""
    k = (f"%screen_matvec.1 = f32[{b},200192]{{1,0:T(8,128)}} "
         f"custom-call(f32[{b},256]{{1,0}} %pad.4, f32[256,200192]{{1,0}} "
         f"%pad.5), custom_call_target=\"tpu_custom_call\"")
    chain = [
        f"%slice_multiply_fusion = f32[{b},200000]{{1,0}} fusion(f32[{b},"
        f"200192]{{1,0}} %screen_matvec.1), kind=kLoop",
        f"%reshape = f32[{b},20000,10]{{2,1,0}} reshape(f32[{b},200000]"
        f"{{1,0}} %slice_multiply_fusion)",
        f"%reduce_sum.7 = f32[{b},20000]{{1,0}} reduce(f32[{b},20000,10]"
        f"{{2,1,0}} %reshape, f32[] %constant.3), dimensions={{2}}",
        f"%sqrt.1 = f32[{b},20000]{{1,0}} sqrt(f32[{b},20000]{{1,0}} "
        f"%reduce_sum.7)",
    ]
    ops = [("%pad.5 = f32[256,200192]{1,0} pad(f32[250,200000]{1,0} %X.1)",
            t0, 600e3, ""), (k, t0 + 1e6, kernel_us * 1e3, "")]
    ops += [(op, t0 + 2e6 + i, reduce_us * 1e3 / len(chain), "")
            for i, op in enumerate(chain)]
    ops.append((other or "%reshape.50 = f32[8,2048,10] reshape(f32[8,20480]"
                " %get-tuple-element.704)", t0 + 3e6, 280e3, ""))
    return ops


def _record(ops, groups=M, padded_b=8):
    session = {"rule": "edpp", "groups": groups} if groups else {}
    steps = [{"x_passes": 1, "n_kept": 300}, {"x_passes": 1, "n_kept": 500},
             {"x_passes": 0, "n_kept": 0}]
    return {
        "window": [0.0, 10.0],
        "config": {"generator": {"params": {"n": N, "p": P}},
                   "session": session},
        "dispatches": [{"t": 1.0, "padded_b": padded_b, "steps": steps}],
        "device": {"n_devices": 1, "ops": ops,
                   "peaks": {"hbm_bytes_per_s": HBM}},
    }


def _read(name, record):
    return harness.load_module(
        ROOT / "bench" / "layer_metrics" / f"{name}.py").read(record)


def test_kept_groups_and_passes_are_means_over_live_steps():
    rec = _record({})
    assert _read("screen.kept_groups_frac", rec) == pytest.approx(0.02)
    assert _read("screen.x_passes_per_step", rec) == pytest.approx(1.0)
    assert _read("screen.kept_groups_frac", _record({}, groups=None)) is None


def test_group_screen_roofline_is_kernel_and_reduction():
    # two calls: kernel 250 + 290 us, reduction 170 + 190 us; X's padding
    # before each and the next program's op after it are not the screen
    ops = {"/device:TPU:0": _screen(1e7, 290.0, 190.0) + _screen(0.0, 250.0,
                                                                 170.0)}
    got = _read("kernel.group_screen_roofline", _record(ops))
    moved = 4 * (N * P + 8 * N + 8 * P) + 4 * (8 * P + 8 * (P // M))
    assert got == pytest.approx(100.0 * moved / HBM / 450e-6)
    assert got == pytest.approx(57.915615, rel=1e-7)


def test_group_screen_roofline_counts_the_scope_where_the_trace_has_it():
    ops = {"/device:TPU:0": _screen(0.0, 250.0, 0.0)[:2] + [
        ("%fusion.3 = f32[8,20000] fusion(f32[8,200192] %screen_matvec.1)",
         2e6, 100e3, "jit(group_screen_scores)/screen/group_reduce/sqrt"),
        ("%fusion.4 = f32[8,20000] fusion(f32[8,20000] %param.2)", 2.2e6,
         100e3, "jit(group_screen_scores)/screen/group_reduce/reduce_sum")]}
    got = _read("kernel.group_screen_roofline", _record(ops))
    moved = 4 * (N * P + 8 * N + 8 * P) + 4 * (8 * P + 8 * (P // M))
    assert got == pytest.approx(100.0 * moved / HBM / 450e-6)


def test_group_screen_roofline_reads_nothing_without_the_batched_screen():
    # a program that screens the batch's queries one by one (kernel calls
    # of one centre in a dispatch of 8), or a trace without the reduction
    single = {"/device:TPU:0": _screen(0.0, 250.0, 170.0, b=1)}
    assert _read("kernel.group_screen_roofline", _record(single)) is None
    bare = {"/device:TPU:0": _screen(0.0, 250.0, 170.0)[:2]}
    assert _read("kernel.group_screen_roofline", _record(bare)) is None
    rec = _record({"/device:TPU:0": _screen(0.0, 250.0, 170.0)})
    rec["device"]["peaks"] = None
    assert _read("kernel.group_screen_roofline", rec) is None


# ---------------------------------------------------------------------------
# the cell through the harness, at 250 x 4000 on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture
def small_root(tmp_path, monkeypatch):
    """A checkout holding the benchmark with the cell's configuration cut
    to p = 4000 and its backlog and warm-up to fit a CPU test."""
    monkeypatch.setenv("REPRO_SCREEN_BACKEND", "jnp")
    monkeypatch.setenv("REPRO_SOLVER_BACKEND", "jnp")
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench" / "configs" / "gsynth.m10.json"
    config = json.loads(path.read_text())
    config["generator"]["params"]["p"] = 4000
    path.write_text(json.dumps(config))
    path = root / "bench" / "mixes" / "group-backlog-half.json"
    mix = json.loads(path.read_text())
    mix["arrivals"]["count"] = 48
    mix["warmup"]["batches"] = 3
    path.write_text(json.dumps(mix))
    return root


def _run(root, seed, trace):
    cell = harness.load_cell(CELL, root)
    out = harness.run_cell(cell, seed, 4.0, trace,
                           t_process=time.perf_counter(), require_tpu=False)
    return out, harness.is_correct(out["check"])


def test_small_run_is_correct_and_screens_once_a_step(small_root):
    out, ok = _run(small_root, 2 ** 31 + 101, trace=False)
    assert ok, out["check"]
    assert out["failed"] == 0
    assert out["metrics"]["qps"]["value"] > 0
    assert out["check"]["compared"]["value"] == 16
    out, ok = _run(small_root, 2 ** 31 + 103, trace=True)
    assert ok, out["check"]
    got = out["metrics"]
    assert got["screen.x_passes_per_step"]["value"] == 1.0
    assert 0 < got["screen.kept_groups_frac"]["value"] < 1
    assert "session.compiles.sat" in got
    # no device plane and no peaks on the CPU
    assert "kernel.group_screen_roofline" not in got
    assert "screen.kept_frac" not in got


def test_control_runs_the_group_reference(small_root):
    cell = harness.load_cell(CELL, small_root)
    got = calibrate.control(cell, 7, "high")
    assert got["of"] == 16
    assert set(got["check"]) == {"gap", "lam_err"}
    assert all(np.isfinite(v) for v in got["check"].values())
