"""The readers of the program's spans and step counters, on a hand-made
record; each reads nothing where the program records nothing."""

import pytest

from bench import harness

from conftest import ROOT


def _step(**kw):
    s = {"x_passes": 1, "n_kept": 30, "screen_time_s": 0.003,
         "solve_time_s": 0.014, "solver_iters": 180,
         "host_syncs": 9, "host_sync_s": 0.004, "gather_time_s": 0.0005,
         "copyout_time_s": 0.002, "state_time_s": 0.0003,
         "step_time_s": 0.02, "compiles": 0}
    s.update(kw)
    return s


def _record(steps_a, steps_b):
    return {
        "window": [0.0, 1.0],
        "dispatches": [
            {"batch_id": 0, "t": 0.1, "t_done": 0.2, "n_live": 8,
             "padded_b": 8, "steps": steps_a},
            {"batch_id": 1, "t": 0.3, "t_done": 0.45, "n_live": 8,
             "padded_b": 8, "steps": steps_b},
            # after the window: in the trace, not in the window's means
            {"batch_id": 2, "t": 1.5, "t_done": 1.6, "n_live": 8,
             "padded_b": 8, "steps": [_step()]},
        ],
        "device": None,
    }


def _read(name, record):
    return harness.load_module(
        ROOT / "bench" / "layer_metrics" / f"{name}.py").read(record)


def test_step_counters_are_means_over_live_steps():
    trivial = _step(x_passes=0, host_syncs=0, host_sync_s=0.0,
                    step_time_s=0.0001)
    rec = _record([_step(host_syncs=8), trivial],
                  [_step(host_syncs=12, copyout_time_s=0.004)])
    assert _read("path.syncs_per_step", rec) == pytest.approx(10.0)
    assert _read("path.sync_ms_per_step", rec) == pytest.approx(4.0)
    assert _read("path.gather_ms_per_step", rec) == pytest.approx(0.5)
    assert _read("path.copyout_ms_per_step", rec) == pytest.approx(3.0)


def test_outside_steps_is_the_dispatch_less_its_steps():
    rec = _record([_step(step_time_s=0.06), _step(step_time_s=0.01)],
                  [_step(step_time_s=0.1)])
    # (0.1 - 0.07) and (0.15 - 0.1) s: mean 40 ms; all steps count here,
    # trivial or not, since each is inside the dispatch
    assert _read("path.outside_steps_ms", rec) == pytest.approx(40.0)


@pytest.mark.parametrize("name", [
    "path.syncs_per_step", "path.sync_ms_per_step",
    "path.gather_ms_per_step", "path.copyout_ms_per_step",
    "path.outside_steps_ms"])
def test_a_program_without_the_counters_reads_nothing(name):
    old = {"x_passes": 1, "n_kept": 30, "screen_time_s": 0.003,
           "solve_time_s": 0.014, "solver_iters": 180}
    assert _read(name, _record([old], [dict(old)])) is None
