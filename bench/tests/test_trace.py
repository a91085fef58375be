"""The trace reduction on a small recorded trace."""

import pytest

from bench import trace

DEV = "/device:TPU:0"
OPS = trace.OPS_LINE
# (plane, line, name, start_ns, dur_ns, meta)
# names as a v5e trace gives them (cut)
KERN = "%screen_matvec.1 = f32[8,50176] custom-call(f32[8,1024] %pad.0)"
USE = "%slice.2 = f32[8,50000] slice(f32[8,50176] %screen_matvec.1)"
EVENTS = [
    (DEV, OPS, KERN, 0.0, 100.0, ""),
    (DEV, OPS, "fusion.1", 50.0, 100.0, ""),       # overlaps the kernel
    (DEV, OPS, KERN, 400.0, 100.0, ""),            # 250 ns gap before it
    (DEV, OPS, USE, 450.0, 20.0, ""),              # inside the kernel
    (DEV, "XLA Modules", "jit_step", 0.0, 600.0, ""),  # not an op line
    ("/device:TPU:1", OPS, KERN, 0.0, 300.0, ""),
    ("/host:CPU", "python", "bench.dispatch", 0.0, 10_000.0, ""),
    ("/host:CPU", "python", "TransferToHost", 160.0, 200.0, ""),
]


def test_union_counts_overlap_once():
    assert trace.union_ns([(0, 100), (50, 100), (400, 100), (450, 20)]) \
        == pytest.approx(250.0)
    assert trace.union_ns([]) == 0.0


def test_busy_is_averaged_over_devices():
    ops = trace.device_ops(EVENTS)
    assert sorted(ops) == [DEV, "/device:TPU:1"]
    # TPU:0 busy 150 + 100 = 250 ns, TPU:1 busy 300 ns
    assert trace.busy_s(ops) == pytest.approx(275e-9)


def test_kernel_time_by_name():
    ops = trace.device_ops(EVENTS)
    assert sorted(trace.custom_call_times(ops, "screen_matvec")) == \
        pytest.approx([100e-9, 100e-9, 300e-9])   # not the slice using it
    assert trace.custom_call_times(ops, "fista_step") == []


def test_idle_gap_named_by_the_host_span_inside_it():
    reduced = trace.reduce(EVENTS)
    assert reduced["n_devices"] == 2
    # the one gap on TPU:0 is [150, 400): the transfer covers it, the
    # whole dispatch (far longer than the gap) does not name it
    assert reduced["idle_gaps"] == [["TransferToHost", pytest.approx(250e-9)]]
    names = [name for name, _ in reduced["device_ops"]]
    assert names[0] == KERN


def test_reads_a_recorded_profile(tmp_path):
    """A real profile written here (host planes only on the CPU)."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    events = trace.read_xplane(str(tmp_path))
    assert any(e[2] == "bench.dispatch" for e in events)
    assert trace.reduce(events)["busy_s"] >= 0.0
