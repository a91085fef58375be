"""Deterministic tests for the continuous-batching serve loop (ISSUE 6).

Every policy branch of :mod:`repro.launch.serve_loop` runs under a
:class:`VirtualClock` — time moves only when the loop decides to wait, so
there are NO sleeps and NO wall-clock assertions anywhere in this module:

  * batch formation: fill-target, deadline-expiry partial batches, drain
    on source exhaustion, pow-2 padding;
  * backpressure: a full bounded queue stalls admissions (visible as
    ``t_admit > t_arrive``) without dropping queries;
  * pipelining: ``max_in_flight`` batches ride concurrently and retire in
    COMPLETION order (a fast batch 1 beats a slow batch 0 home);
  * replay determinism: the same arrival script produces an identical
    :class:`DispatchRecord` trace and identical per-query results;
  * fault isolation: a poison query is rejected at admission, or — when
    admission validation is off — its failed batch is split and re-served
    one query at a time, neighbours unharmed (checked bit-for-bit against
    direct ``session.path`` calls);
  * accounting: p50/p99 latency from scripted timelines matches
    hand-computed values, via the ONE :func:`percentile` definition that
    ``benchmarks/common.py`` re-exports.

Scheduler tests use a :class:`FakeExecutor`; the handful of end-to-end
tests at the bottom run a real (tiny) :class:`LassoSession`.
"""

import math

import numpy as np
import pytest

from repro.launch import serve_loop as sl


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

class FakeExecutor:
    """Synchronous scheduler-test executor: each live lane's result is its
    own query row (so tests can check routing), convergence is scripted by
    query content, and — mimicking the real executor's failure capture — a
    batch containing a non-finite row fails wholesale when ``fail_on_nan``
    (that is what ``session.path`` does to a NaN query's λ grid)."""

    def __init__(self, *, fail_on_nan=False, unconverged_mark=None):
        self.fail_on_nan = fail_on_nan
        self.unconverged_mark = unconverged_mark
        self.dispatches = []          # (batch_id, n_live, padded_b, now)

    def dispatch(self, Y, n_live, batch_id, now):
        Y = np.asarray(Y)
        self.dispatches.append((batch_id, n_live, Y.shape[0], now))
        if self.fail_on_nan and not np.isfinite(Y[:n_live]).all():
            return sl.ImmediateHandle(
                failure=ValueError("poisoned lambda grid"))
        lanes = []
        for b in range(n_live):
            conv = not (self.unconverged_mark is not None
                        and Y[b, 0] == self.unconverged_mark)
            lanes.append(sl.LaneResult(result=Y[b].copy(), converged=conv))
        return sl.ImmediateHandle(lanes=lanes)


def qrow(i, n=4):
    """Distinct, recognisable query vector for query id i."""
    v = np.full(n, float(i))
    v[0] = float(i)
    return v


def eager(count, t=0.0):
    return sl.ScriptedArrivals([(t, qrow(i)) for i in range(count)])


def run_loop(arrivals, executor, policy, **kw):
    clock = kw.pop("clock", None) or sl.VirtualClock()
    loop = sl.ServeLoop(arrivals, executor, policy=policy, clock=clock, **kw)
    return loop.run()


# ---------------------------------------------------------------------------
# clocks + arrivals + policy validation
# ---------------------------------------------------------------------------

def test_virtual_clock_only_moves_forward():
    c = sl.VirtualClock()
    c.advance_to(1.5)
    assert c.now() == 1.5
    with pytest.raises(ValueError, match="backwards"):
        c.advance_to(1.0)


def test_scripted_arrivals_validate_order():
    with pytest.raises(ValueError, match="non-decreasing"):
        sl.ScriptedArrivals([(1.0, qrow(0)), (0.5, qrow(1))])
    a = sl.ScriptedArrivals([(0.0, qrow(0)), (2.0, qrow(1))])
    assert a.peek_time() == 0.0
    a.pop(0.0)
    with pytest.raises(RuntimeError, match="not arrived"):
        a.pop(1.0)                     # query 1 arrives at t=2


def test_policy_validation():
    with pytest.raises(ValueError, match="queue_cap"):
        sl.ServePolicy(b_max=8, queue_cap=4)
    with pytest.raises(ValueError, match="pad"):
        sl.ServePolicy(pad="mirror")
    with pytest.raises(ValueError, match="b_max"):
        sl.ServePolicy(b_max=0)
    with pytest.raises(ValueError, match="max_in_flight"):
        sl.ServePolicy(max_in_flight=0)


def test_padded_sizes():
    pow2 = sl.ServePolicy(b_max=16, pad="pow2")
    assert [pow2.padded_size(k) for k in (1, 2, 3, 5, 9, 16)] \
        == [1, 2, 4, 8, 16, 16]
    assert sl.ServePolicy(b_max=16, pad="full").padded_size(3) == 16
    assert sl.ServePolicy(b_max=16, pad="none").padded_size(3) == 3


# ---------------------------------------------------------------------------
# batch formation
# ---------------------------------------------------------------------------

def test_fill_target_dispatch():
    """8 eager queries at b_max=4 → two full 'fill' batches, zero waiting."""
    ex = FakeExecutor()
    rep = run_loop(eager(8), ex,
                   sl.ServePolicy(b_max=4, deadline_s=1.0, queue_cap=8))
    assert [r.reason for r in rep.trace] == ["fill", "fill"]
    assert [r.qids for r in rep.trace] == [(0, 1, 2, 3), (4, 5, 6, 7)]
    assert all(r.n_live == r.padded_b == 4 for r in rep.trace)
    # synchronous executor + virtual clock: everything completes at t=0
    assert rep.latencies_s == [0.0] * 8
    for t in rep.tickets:              # results routed to the right ticket
        np.testing.assert_array_equal(t.result, qrow(t.qid))
    s = rep.summary()
    assert s["n_ok"] == 8 and s["n_errors"] == 0
    assert s["mean_batch_fill"] == 1.0 and s["deadline_dispatch_frac"] == 0.0


def test_deadline_fires_partial_batch():
    """3 queries at t=0 with a 4th far away: the deadline (not the fill
    target) dispatches the partial batch, pow-2 padded 3 → 4."""
    arr = sl.ScriptedArrivals([(0.0, qrow(0)), (0.0, qrow(1)),
                               (0.0, qrow(2)), (10.0, qrow(3))])
    rep = run_loop(arr, FakeExecutor(),
                   sl.ServePolicy(b_max=4, deadline_s=0.5, queue_cap=8))
    first, second = rep.trace
    assert (first.reason, first.n_live, first.padded_b, first.t) \
        == ("deadline", 3, 4, 0.5)
    # the straggler arrives into an exhausted source → drain, unpadded
    # (a 1-live batch is a batch of one)
    assert (second.reason, second.n_live, second.padded_b, second.t) \
        == ("drain", 1, 1, 10.0)
    assert [t.latency_s for t in rep.tickets] == [0.5, 0.5, 0.5, 0.0]
    assert rep.summary()["deadline_dispatch_frac"] == 0.5


def test_drain_when_source_exhausted():
    """With no more arrivals possible, waiting for the deadline would only
    add latency — the loop drains immediately."""
    rep = run_loop(eager(3), FakeExecutor(),
                   sl.ServePolicy(b_max=8, deadline_s=100.0, queue_cap=8))
    assert [(r.reason, r.n_live, r.padded_b) for r in rep.trace] \
        == [("drain", 3, 4)]
    assert rep.latencies_s == [0.0] * 3


# ---------------------------------------------------------------------------
# backpressure + pipelining
# ---------------------------------------------------------------------------

def test_backpressure_stalls_admission_without_loss():
    """12 eager queries into a cap-4 queue with one slow in-flight slot:
    the last wave waits UPSTREAM (t_admit > t_arrive), nothing is dropped."""
    ex = sl.DelayedExecutor(FakeExecutor(), lambda n_live, bid: 1.0)
    rep = run_loop(eager(12), ex,
                   sl.ServePolicy(b_max=4, deadline_s=math.inf, queue_cap=4,
                                  max_in_flight=1))
    s = rep.summary()
    assert s["n_ok"] == 12 and s["n_errors"] == 0
    assert s["max_queue_len"] == 4
    # queries 0-7 were admitted at t=0 (wave 2 entered as wave 1 dispatched);
    # queries 8-11 stalled until batch 0 retired at t=1
    assert [t.stalled for t in rep.tickets] == [False] * 8 + [True] * 4
    assert s["backpressure_waits"] == 4
    assert [t.t_admit for t in rep.tickets] == [0.0] * 8 + [1.0] * 4
    # service is 1s/batch, single slot → batches retire at t=1, 2, 3
    assert [t.t_complete for t in rep.tickets] \
        == [1.0] * 4 + [2.0] * 4 + [3.0] * 4
    assert rep.wall_time_s == 3.0


def test_out_of_order_completion():
    """Batch 0 is slow, batch 1 fast: retirement happens in COMPLETION
    order — the loop never head-of-line-blocks on an older batch."""
    done_order = []
    ex = sl.DelayedExecutor(FakeExecutor(),
                            lambda n_live, bid: {0: 2.0, 1: 0.5}[bid])
    rep = run_loop(eager(4), ex,
                   sl.ServePolicy(b_max=2, deadline_s=math.inf, queue_cap=8,
                                  max_in_flight=2),
                   on_complete=lambda t: done_order.append(t.qid))
    assert done_order == [2, 3, 0, 1]
    assert [t.t_complete for t in rep.tickets] == [2.0, 2.0, 0.5, 0.5]
    assert rep.wall_time_s == 2.0


def test_replay_determinism():
    """The core serving contract: the same arrival script through the same
    policy yields an IDENTICAL dispatch trace and identical per-query
    results — bit-for-bit, timestamps included."""
    def one_run():
        arr = sl.ScriptedArrivals(
            [(i * 0.01, qrow(i)) for i in range(11)])
        ex = sl.DelayedExecutor(FakeExecutor(),
                                lambda n_live, bid: 0.03 + 0.01 * (bid % 2))
        return run_loop(arr, ex,
                        sl.ServePolicy(b_max=4, deadline_s=0.05,
                                       queue_cap=6, max_in_flight=2))

    a, b = one_run(), one_run()
    assert a.trace == b.trace          # DispatchRecord is frozen/comparable
    for ta, tb in zip(a.tickets, b.tickets):
        assert (ta.qid, ta.t_admit, ta.t_dispatch, ta.t_complete,
                ta.batch_id, ta.error) \
            == (tb.qid, tb.t_admit, tb.t_dispatch, tb.t_complete,
                tb.batch_id, tb.error)
        np.testing.assert_array_equal(ta.result, tb.result)
    assert a.summary() == b.summary()


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

def test_poison_rejected_at_admission():
    bad = qrow(1)
    bad[2] = np.nan
    arr = sl.ScriptedArrivals([(0.0, qrow(0)), (0.0, bad), (0.0, qrow(2))])
    rep = run_loop(arr, FakeExecutor(),
                   sl.ServePolicy(b_max=4, queue_cap=8))
    t_bad = rep.tickets[1]
    assert t_bad.error == "non-finite query rejected at admission"
    assert t_bad.t_complete == t_bad.t_admit
    # the poison never joins a batch; its neighbours are served normally
    assert all(1 not in r.qids for r in rep.trace)
    s = rep.summary()
    assert s["n_ok"] == 2 and s["n_errors"] == 1
    np.testing.assert_array_equal(rep.tickets[0].result, qrow(0))
    np.testing.assert_array_equal(rep.tickets[2].result, qrow(2))


def test_poison_batch_split_and_isolated():
    """Admission validation off → the poison reaches a batch, the batch
    fails, and the loop splits it: every member re-served alone, only the
    poison's ticket carries the error."""
    bad = qrow(2)
    bad[1] = np.nan
    arr = sl.ScriptedArrivals(
        [(0.0, qrow(0)), (0.0, qrow(1)), (0.0, bad), (0.0, qrow(3))])
    ex = FakeExecutor(fail_on_nan=True)
    rep = run_loop(arr, ex,
                   sl.ServePolicy(b_max=4, queue_cap=8,
                                  validate_admission=False))
    reasons = [r.reason for r in rep.trace]
    assert reasons == ["fill", "isolate", "isolate", "isolate", "isolate"]
    assert all(r.n_live == 1 for r in rep.trace[1:])
    s = rep.summary()
    assert s["n_ok"] == 3 and s["n_errors"] == 1
    assert "ValueError" in rep.tickets[2].error
    for qid in (0, 1, 3):
        t = rep.tickets[qid]
        assert t.ok
        np.testing.assert_array_equal(t.result, qrow(qid))


def test_unconverged_lane_reported_not_failed():
    """A query the solver gave up on is still served (best-effort β) but
    flagged per-ticket and counted in the summary."""
    ex = FakeExecutor(unconverged_mark=1.0)   # qrow(1)[0] == 1.0
    rep = run_loop(eager(3), ex, sl.ServePolicy(b_max=4, queue_cap=8))
    assert [t.converged for t in rep.tickets] == [True, False, True]
    assert all(t.ok for t in rep.tickets)
    s = rep.summary()
    assert s["n_unconverged"] == 1 and s["n_errors"] == 0


# ---------------------------------------------------------------------------
# latency accounting
# ---------------------------------------------------------------------------

def test_percentile_hand_computed():
    assert sl.percentile([1.0, 2.0, 3.0, 4.0], 50.0) == 2.5
    assert sl.percentile([5.0], 99.0) == 5.0
    assert sl.percentile([3.0, 1.0, 2.0], 0.0) == 1.0    # sorts internally
    assert sl.percentile([3.0, 1.0, 2.0], 100.0) == 3.0
    # rank (m-1)·q/100 = 1.98 → 0.02·v[1] + 0.98·v[2]
    assert sl.percentile([0.1, 0.2, 0.4], 99.0) == pytest.approx(0.396)
    assert math.isnan(sl.percentile([], 50.0))
    with pytest.raises(ValueError):
        sl.percentile([1.0], 101.0)


def test_percentile_matches_numpy_and_bench_reexport():
    from benchmarks import common
    assert common.percentile is sl.percentile   # ONE definition everywhere
    r = np.random.default_rng(3)
    vals = r.uniform(0, 10, 37).tolist()
    for q in (0.0, 12.5, 50.0, 90.0, 99.0, 100.0):
        assert sl.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12)


def test_latency_summary_from_scripted_timeline():
    """b_max=1 with three in-flight slots: three solo batches with scripted
    service times 0.1/0.2/0.4s — p50, p99 and queries/sec by hand."""
    ex = sl.DelayedExecutor(FakeExecutor(),
                            lambda n_live, bid: [0.1, 0.2, 0.4][bid])
    rep = run_loop(eager(3), ex,
                   sl.ServePolicy(b_max=1, pad="none", queue_cap=8,
                                  max_in_flight=3))
    assert sorted(rep.latencies_s) == [0.1, 0.2, 0.4]
    s = rep.summary()
    assert s["p50_latency_s"] == pytest.approx(0.2)
    assert s["p99_latency_s"] == pytest.approx(0.396)
    assert s["queries_per_sec"] == pytest.approx(3 / 0.4)
    assert s["wall_time_s"] == pytest.approx(0.4)


# ---------------------------------------------------------------------------
# end-to-end against a real (tiny) session
# ---------------------------------------------------------------------------

def _tiny_session(n=25, p=64, seed=0, **cfg_kw):
    import jax.numpy as jnp
    from repro.core import LassoSession, PathConfig
    from repro.data import design_matrix
    X = design_matrix(n, p, seed=seed)
    cfg = PathConfig(**cfg_kw) if cfg_kw else None
    return LassoSession.fit(jnp.asarray(X, jnp.float32),
                            config=cfg), np.asarray(X)


def _queries(X, count, seed=1):
    r = np.random.default_rng(seed)
    n, p = X.shape
    ys = []
    for _ in range(count):
        beta = np.zeros(p)
        beta[r.choice(p, 5, replace=False)] = r.uniform(-1, 1, 5)
        ys.append(X @ beta + 0.1 * r.standard_normal(n))
    return ys


def test_served_masks_bit_identical_to_direct_session():
    """The exactness contract through the WHOLE serve stack: every served
    query's masks — full fill batch, pow-2-padded partial, and the 1-live
    drain batch (a batch of one) — equal a direct
    ``session.path`` call on the same grid, bit for bit."""
    import jax.numpy as jnp
    sess, X = _tiny_session()
    ys = _queries(X, 7)
    arr = sl.ScriptedArrivals([(0.0, y) for y in ys])
    ex = sl.SessionExecutor(sess, num_lambdas=5, hi_frac=0.95)
    rep = run_loop(arr, ex, sl.ServePolicy(b_max=4, queue_cap=8))
    # 7 queries at b_max=4: fill(4), then drain(3) padded to 4
    assert [(r.reason, r.n_live, r.padded_b) for r in rep.trace] \
        == [("fill", 4, 4), ("drain", 3, 4)]
    assert len(rep.ok_tickets) == 7
    for t in rep.tickets:
        ref = sess.path(jnp.asarray(ys[t.qid]), t.result.lambdas)
        np.testing.assert_array_equal(np.asarray(ref.masks[0]),
                                      np.asarray(t.result.masks))
        # betas agree at solver precision (the BITWISE guarantee is for
        # masks; β is a gap-ε solver iterate — docs/api.md)
        np.testing.assert_allclose(np.asarray(ref.betas[0]),
                                   np.asarray(t.result.betas), atol=1e-3)


def test_poison_query_isolated_real_session():
    """Fault injection end-to-end (ISSUE 6 satellite): one NaN query inside
    a real batch poisons the shared λ-grid machinery; the loop isolates it
    onto its own failed ticket and the neighbours' masks remain
    bit-identical to direct ``session.path`` calls."""
    import jax.numpy as jnp
    sess, X = _tiny_session()
    ys = _queries(X, 4)
    ys[2] = ys[2].copy()
    ys[2][0] = np.nan
    arr = sl.ScriptedArrivals([(0.0, y) for y in ys])
    ex = sl.SessionExecutor(sess, num_lambdas=4, hi_frac=0.95)
    rep = run_loop(arr, ex,
                   sl.ServePolicy(b_max=4, queue_cap=8,
                                  validate_admission=False))
    assert [r.reason for r in rep.trace] \
        == ["fill", "isolate", "isolate", "isolate", "isolate"]
    s = rep.summary()
    assert s["n_ok"] == 3 and s["n_errors"] == 1
    assert rep.tickets[2].error is not None
    for qid in (0, 1, 3):
        t = rep.tickets[qid]
        assert t.ok
        ref = sess.path(jnp.asarray(ys[qid]), t.result.lambdas)
        np.testing.assert_array_equal(np.asarray(ref.masks[0]),
                                      np.asarray(t.result.masks))


def test_unconverged_query_surfaces_on_ticket_real_session():
    """A solver capped far below convergence still serves (best-effort β)
    but reports per-query ``converged=False`` through
    ``PathResult.query_converged`` → ticket → summary."""
    sess, X = _tiny_session(solver_tol=1e-10, max_iter=2)
    ys = _queries(X, 3)
    arr = sl.ScriptedArrivals([(0.0, y) for y in ys])
    ex = sl.SessionExecutor(sess, num_lambdas=4, hi_frac=0.95)
    rep = run_loop(arr, ex, sl.ServePolicy(b_max=4, queue_cap=8))
    s = rep.summary()
    assert s["n_errors"] == 0                     # served, not failed
    assert s["n_unconverged"] == 3                # ...but honestly flagged
    assert all(t.converged is False for t in rep.tickets)
