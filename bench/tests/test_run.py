"""A whole run on the CPU at a tiny size: the sound program is correct, and
the check refuses the program broken underneath in each way a cell of this
benchmark can be broken, and refuses the control."""

import time

import numpy as np
import pytest

from bench import harness, reference


def _run(root, seed=11, seconds=6.0, trace=False, name="tiny.sat"):
    cell = harness.load_cell(name, root)
    out = harness.run_cell(cell, seed, seconds, trace,
                           t_process=time.perf_counter(), require_tpu=False)
    return out, harness.is_correct(out["check"])


def test_sound_run_is_correct(tiny_root):
    out, ok = _run(tiny_root)
    assert ok, out["check"]
    assert out["failed"] == 0
    assert out["metrics"]["qps"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["check"]["compared"]["value"] == 8


def test_open_loop_run_times_every_due_query(tiny_root):
    out, ok = _run(tiny_root, seed=15, name="tiny.steady")
    assert ok, out["check"]
    # 4 queries/s over 6 s: every one of them answered and timed
    assert out["attempted"] == 24
    p50 = out["metrics"]["latency_p50_ms"]["value"]
    p95 = out["metrics"]["latency_p95_ms"]["value"]
    assert 0 < p50 <= p95 < float("inf")
    assert "qps" not in out["metrics"]


def test_traced_run_reads_layer_metrics(tiny_root):
    out, ok = _run(tiny_root, seed=12, trace=True)
    assert ok, out["check"]
    got = out["metrics"]
    for name in ("session.compiles.sat", "screen.kept_frac",
                 "solve.iters_per_step"):
        assert name in got, name
    # no device plane and no peaks on the CPU: no roofline, no idle share
    assert "kernel.screen_roofline" not in got
    assert out["device"]["window_s"] > 0


def _patch_path(monkeypatch, alter):
    from repro.core import session as sess_mod
    real = sess_mod.LassoSession.path

    def broken(self, Y, *a, **k):
        res = real(self, Y, *a, **k)
        alter(res)
        return res

    monkeypatch.setattr(sess_mod.LassoSession, "path", broken)


def _alter_answer(res):          # one query's answer altered where made
    res.betas[0, -1] *= 1.05


def _half_batch(res):            # half of the batch left out: copies
    B = res.betas.shape[0]
    if B > 1:
        res.betas[B // 2:] = res.betas[:1]


def _state_unchanged(res):       # each step hands back the step before
    res.betas[:, 1:] = res.betas[:, :-1].copy()


@pytest.mark.parametrize("fault", [_alter_answer, _half_batch,
                                   _state_unchanged])
def test_faults_are_refused(tiny_root, monkeypatch, fault):
    _patch_path(monkeypatch, fault)
    out, ok = _run(tiny_root, seed=13)
    assert not ok, out["check"]


def _reference_in_place(monkeypatch, precision):
    """The plain reference put in the program's place, claiming every
    answer converged, as a program that computed at a lower precision
    unawares would."""
    from repro.core import session as sess_mod
    from repro.core.path import PathResult

    def path(self, Y, lambdas=None, *, num_lambdas=100, lo_frac=0.05,
             hi_frac=1.0, config=None):
        Y = np.atleast_2d(np.asarray(Y))
        grid = {"num_lambdas": num_lambdas, "lo_frac": lo_frac,
                "hi_frac": hi_frac}
        lams, betas, masks, conv = reference.reference_path(
            self.X, Y, grid, precision=precision, tol=self.config.solve.tol)
        return PathResult(lambdas=lams, betas=betas, stats=[], masks=masks,
                          query_converged=np.ones_like(conv))

    monkeypatch.setattr(sess_mod.LassoSession, "path", path)


def test_reference_in_place_at_highest_is_correct(tiny_root, monkeypatch):
    _reference_in_place(monkeypatch, "highest")
    out, ok = _run(tiny_root, seed=14)
    assert ok, out["check"]


def test_control_is_refused(tiny_root, monkeypatch):
    # the reference at three bfloat16 passes ("high") meets this cell's
    # guarantee within the limits (PERF.md, Open questions); one pass is
    # the precision below it that the answers show
    _reference_in_place(monkeypatch, "bfloat16")
    out, ok = _run(tiny_root, seed=14)
    assert not ok, out["check"]
