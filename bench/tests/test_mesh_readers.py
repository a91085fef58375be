"""The device readers on a hand-made traced record, on one chip and on a
mesh: the screen kernel's roofline counts one device's call, and the
collectives' time is per device and per live step."""

import pytest

from bench import harness, roofline

from conftest import ROOT

HBM = 819e9
N, P = 784, 50000


def _read(name, record):
    return harness.load_module(
        ROOT / "bench" / "layer_metrics" / f"{name}.py").read(record)


def _kernel(i):
    return f"%screen_matvec.{i} = f32[8,50176] custom-call(f32[8,1024] %pad)"


def _record(ops, padded_b, mesh=None, steps=2):
    session = {"rule": "edpp"}
    if mesh:
        session["mesh"] = mesh
    step = {"x_passes": 1}
    return {
        "window": [0.0, 10.0],
        "config": {"generator": {"params": {"n": N, "p": P}},
                   "session": session},
        "dispatches": [
            {"t": 1.0, "padded_b": padded_b,
             "steps": [step] * steps + [{"x_passes": 0}]},
            # due after the window closed, still inside the trace
            {"t": 10.5, "padded_b": padded_b, "steps": [step] * steps},
        ],
        "device": {"n_devices": len(ops), "ops": ops,
                   "peaks": {"hbm_bytes_per_s": HBM}},
    }


def test_roofline_on_one_chip_is_the_whole_call():
    # 200 us and 300 us calls of the (8, 784) x (784, 50000) matvec
    ops = {"/device:TPU:0": [(_kernel(1), 0.0, 200e3, ""),
                             (_kernel(2), 1e6, 300e3, "")]}
    got = _read("kernel.screen_roofline", _record(ops, 8))
    want = 100.0 * 4 * (N * P + 8 * N + 8 * P) / HBM / 250e-6
    assert got == pytest.approx(want)
    assert got == pytest.approx(77.374890, rel=1e-7)


@pytest.mark.parametrize("mesh, b_local, p_local", [
    ({"axes": ["query", "feature"], "shape": [4, 1]}, 8, P),
    ({"axes": ["query", "feature"], "shape": [2, 2]}, 16, P // 2),
    # a query axis that does not divide the batch: the batch is whole
    ({"axes": ["query", "feature"], "shape": [3, 1]}, 32, P),
    ({"axes": ["feature"], "shape": [4]}, 32, P // 4),
])
def test_roofline_on_a_mesh_counts_one_devices_call(mesh, b_local, p_local):
    ops = {f"/device:TPU:{i}": [(_kernel(1), 0.0, 250e3, "")]
           for i in range(4)}
    got = _read("kernel.screen_roofline", _record(ops, 32, mesh))
    least = roofline.matvec_bytes(N, p_local, b_local) / HBM
    assert got == pytest.approx(100.0 * least / 250e-6)


GATHER = "%all-gather.3 = f32[32,784] all-gather(f32[8,784] %p0), dimensions={0}"
START = ("%all-reduce-start.1 = f32[32] all-reduce-start(f32[32] %x), "
         "to_apply=%add")
DONE = "%all-reduce-done.1 = f32[32] all-reduce-done(f32[32] %all-reduce-start.1)"
PERMUTE = ("%collective-permute.2 = f32[8] collective-permute(f32[8] %y), "
           "source_target_pairs={{0,1}}")
# names that hold a collective's name and are not one
FUSION = "%fusion.7 = f32[32,784] fusion(f32[32,784] %all-gather.3), kind=kLoop"
COPY = "%copy.1 = f32[784,50000] copy(f32[784,50000] %all-reduce-done.1)"


def test_collective_time_per_device_and_live_step():
    ops = {
        "/device:TPU:0": [(GATHER, 0.0, 1e6, ""), (START, 2e6, 0.5e6, ""),
                          (DONE, 3e6, 1.5e6, ""), (FUSION, 5e6, 9e6, ""),
                          (COPY, 20e6, 9e6, "")],
        "/device:TPU:1": [(PERMUTE, 0.0, 1e6, ""), (FUSION, 2e6, 9e6, "")],
    }
    # 3 ms on TPU:0, 1 ms on TPU:1: 2 ms a device, over 4 live steps
    got = _read("device.collective_ms_per_step", _record(ops, 32))
    assert got == pytest.approx(0.5)


def test_no_collective_reads_zero_and_no_device_nothing():
    ops = {"/device:TPU:0": [(FUSION, 0.0, 9e6, "")]}
    assert _read("device.collective_ms_per_step", _record(ops, 8)) == 0.0
    rec = _record(ops, 8)
    rec["device"] = None
    assert _read("device.collective_ms_per_step", rec) is None
