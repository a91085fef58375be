"""Spans and step counters of the served path (core/tracing.py): the trace
of one dispatch, the counters each step records, the layer scopes on the
device ops, and answers unchanged with the profiler running."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import LassoSession
from repro.core import PathConfig, ScreenSpec, engine, tracing
from repro.core import lasso, path as path_mod, screening as scr
from repro.core import solver as solver_mod
from repro.kernels import group_screen_scores, ops, screen_matvec
from repro.launch import serve_loop as sl

N, P, B, K = 64, 512, 4, 4

# every span of one dispatch and the span it nests in
PARENT = {
    "path.prologue": "serve.dispatch",
    "path.step": "serve.dispatch",
    "serve.lanes": "serve.dispatch",
    "path.screen": "path.step",
    "path.solve": "path.step",
    "path.copyout": "path.step",
    "path.state": "path.step",
    "path.gather": "path.solve",
    "solve.lipschitz": "path.solve",
    "solve.iterate": "path.solve",
    "path.scatter": "path.solve",
    "path.kkt": "path.solve",
}


def _problem(n=N, p=P, b=B, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, p)).astype(np.float32)
    Y = (X[:, :10] @ rng.uniform(-1, 1, (10, b))).T.astype(np.float32)
    return X, Y + 0.01 * rng.standard_normal(Y.shape).astype(np.float32)


@pytest.fixture(scope="module")
def served():
    """A session with the KKT loop on (so that ``path.kkt`` runs) and an
    executor over it, warmed up once."""
    X, Y = _problem()
    cfg = PathConfig(screen=ScreenSpec(rule="edpp", paranoid=True))
    sess = LassoSession.fit(X, config=cfg)
    ex = sl.SessionExecutor(sess, num_lambdas=K, hi_frac=0.95)
    ex.dispatch(Y, B, -1, 0.0).result()
    return sess, ex, Y


def _dispatch(sess, ex, Y, batch_id=0):
    sess.reset_solver_cache()
    return ex.dispatch(Y, B, batch_id, 0.0).result()[0].result


def _host_events(log_dir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PARENT or ev.name in ("serve.dispatch",
                                                    "path.sync"):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _parent(ev, events):
    """The shortest span on the same thread that encloses ``ev``."""
    line, _, t0, t1, _ = ev
    enclosing = [e for e in events if e is not ev and e[0] == line
                 and e[2] <= t0 and t1 <= e[3]]
    return min(enclosing, key=lambda e: e[3] - e[2], default=None)


def test_dispatch_trace_holds_every_span_nested(served, tmp_path):
    sess, ex, Y = served
    with jax.profiler.trace(str(tmp_path)):
        res = _dispatch(sess, ex, Y, batch_id=7)
    events = _host_events(str(tmp_path))
    names = {e[1] for e in events}
    assert set(PARENT) | {"serve.dispatch", "path.sync"} <= names
    (dispatch,) = [e for e in events if e[1] == "serve.dispatch"]
    assert dispatch[4] == {"batch_id": 7}
    for ev in events:
        if ev[1] in PARENT:
            assert _parent(ev, events)[1] == PARENT[ev[1]], ev
    steps = sorted((e for e in events if e[1] == "path.step"),
                   key=lambda e: e[2])
    assert [e[4] for e in steps] == [{"k": k} for k in range(K)]
    # each step's counted syncs are the path.sync spans inside it
    for ev, st in zip(steps, res.stats):
        inside = [e for e in events if e[1] == "path.sync"
                  and ev[2] <= e[2] and e[3] <= ev[3]]
        assert len(inside) == st.host_syncs > 0


def test_host_syncs_count_the_fetches_of_each_step(served, monkeypatch):
    sess, ex, Y = served
    calls = []
    real = tracing.fetch

    def counting(x, dtype=None):
        calls.append(getattr(tracing._local, "step", None))
        return real(x, dtype)

    monkeypatch.setattr(tracing, "fetch", counting)
    res = _dispatch(sess, ex, Y)
    steps = [c for c in calls if c is not None]
    assert {c.k for c in steps} <= set(range(K))
    for k, st in enumerate(res.stats):
        assert sum(c.k == k for c in steps) == st.host_syncs
        if st.x_passes:
            assert st.host_syncs > 0 and st.host_sync_s > 0


def test_step_time_holds_its_parts(served):
    sess, ex, Y = served
    res = _dispatch(sess, ex, Y)
    for st in res.stats:
        parts = (st.screen_time_s + st.solve_time_s + st.copyout_time_s
                 + st.state_time_s)
        assert st.step_time_s >= parts > 0
        assert st.solve_time_s >= st.gather_time_s > 0
        assert st.copyout_time_s > 0 and st.state_time_s > 0


def test_a_new_bucket_compiles_and_its_repeat_does_not():
    # shapes no other test uses, so the first path compiles here
    X, Y = _problem(n=48, p=320, b=3, seed=5)
    sess = LassoSession.fit(X)
    first = sess.path(Y, num_lambdas=3, hi_frac=0.9)
    assert first.stats[0].compiles >= 1
    sess.reset_solver_cache()
    again = sess.path(Y, num_lambdas=3, hi_frac=0.9)
    assert [s.bucket for s in again.stats] == [s.bucket for s in first.stats]
    assert [s.compiles for s in again.stats] == [0] * 3


def test_a_live_batched_fista_step_reads_five_times():
    # the mask; the solver's iteration count, gap checks and iteration
    # count again; the step epilogue's one packed read
    X, Y = _problem(seed=1)
    sess = LassoSession.fit(X)
    res = sess.path(Y, num_lambdas=K, hi_frac=0.9)
    assert all(st.n_kept > 0 for st in res.stats)
    assert [st.host_syncs for st in res.stats] == [5] * K


def test_kept_counts_inside_one_bucket_compile_nothing():
    # two grids on one session: new kept counts, the same bucket
    X, Y = _problem(n=56, p=384, b=2, seed=7)
    sess = LassoSession.fit(X)
    first = sess.path(Y, num_lambdas=4, hi_frac=0.6, lo_frac=0.4)
    second = sess.path(Y, num_lambdas=4, hi_frac=0.5, lo_frac=0.3)
    seen = {s.n_kept for s in first.stats}
    assert {s.n_kept for s in second.stats} - seen
    assert {s.bucket for s in second.stats} <= {s.bucket for s in first.stats}
    assert sum(s.compiles for s in second.stats) == 0


def test_a_batched_group_step_screens_once_and_reads_four_times(
        monkeypatch):
    # the batch's λ̄_max is the prologue's one read; a live step reads the
    # mask, the solver's gap checks and iteration count, and the step
    # epilogue's packed summary
    X, Y = _problem(seed=2)
    sess = LassoSession.fit(X, groups=4)
    calls = []
    real = tracing.fetch

    def counting(x, dtype=None):
        calls.append(getattr(tracing._local, "step", None))
        return real(x, dtype)

    monkeypatch.setattr(tracing, "fetch", counting)
    res = sess.path(Y, num_lambdas=K, hi_frac=0.9)
    assert sum(c is None for c in calls) == 1
    assert all(st.n_kept > 0 for st in res.stats)
    for st in res.stats:
        assert (st.batch_size, st.x_passes, st.host_syncs) == (B, 1, 4)
        parts = (st.screen_time_s + st.solve_time_s + st.copyout_time_s
                 + st.state_time_s)
        assert st.step_time_s >= parts > 0


def _state():
    return scr.DualState(theta=jnp.ones((B, N)), lam=jnp.ones((B,)),
                         v1=jnp.ones((B, N)),
                         at_lmax=jnp.zeros((B,), bool),
                         beta_l1=jnp.ones((B,)))


def _lowered():
    X = jnp.ones((N, P))
    Xr = jnp.ones((N, 32))
    idx = jnp.arange(32, dtype=jnp.int32)
    lam = jnp.ones((B,))
    beta = jnp.ones((B, 32))
    return {
        "screen_matvec": ("screen", lambda: screen_matvec.lower(
            X, jnp.ones((B, N)), interpret=True)),
        "group_screen_scores": ("group_reduce", lambda:
                                group_screen_scores.lower(
                                    X, jnp.ones((B, N)), 4, interpret=True)),
        "make_sphere": ("screen", lambda: scr.make_sphere.lower(
            "edpp", jnp.ones((B, N)), lam, _state())),
        "sphere_combine": ("screen", lambda: engine._sphere_combine.lower(
            jnp.ones((B, P)), lam, jnp.ones((P,)), 1e-6)),
        "gather_cols": ("gather", lambda: path_mod._gather_cols.lower(
            X, idx, jnp.ones((32,)), 32)),
        "fista_solve_batched": ("solve", lambda:
                                solver_mod._fista_solve_batched.lower(
                                    ops.BACKENDS["jnp"], Xr,
                                    jnp.ones((B, N)), lam, beta, beta,
                                    1.0, 1e-6, 100, 10)),
        "step_epilogue": ("scatter", lambda: path_mod._step_epilogue.lower(
            beta, jnp.ones((B,), jnp.int32), lam, jnp.ones((B,), bool), Xr,
            idx, jnp.ones((32,)), p=P)),
        "power_iterate": ("solve", lambda: lasso._power_iterate.lower(
            Xr, jnp.ones((32,)), 4)),
        "make_state_batched_fit": ("state", lambda:
                                   engine._make_state_batched_fit.lower(
                                       jnp.ones((B, N)), jnp.ones((B, N)),
                                       jnp.ones((B, P)), lam, lam,
                                       jnp.ones((B, N)))),
    }


@pytest.mark.parametrize("fn", sorted(_lowered()))
def test_device_ops_carry_their_layer_scope(fn):
    scope, lower = _lowered()[fn]
    text = lower().as_text(debug_info=True)
    assert f"/{scope}/" in text


def test_answers_bit_identical_with_the_profiler_on(served, tmp_path):
    sess, ex, Y = served
    off = _dispatch(sess, ex, Y)
    with jax.profiler.trace(str(tmp_path)):
        on = _dispatch(sess, ex, Y)
    assert np.array_equal(on.masks, off.masks)
    assert np.array_equal(on.betas, off.betas)
    assert np.array_equal(on.lambdas, off.lambdas)
