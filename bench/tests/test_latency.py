"""Latency is timed from the due time: a backpressure stall counts."""

import math

import pytest

from bench import harness


def _stalled_run():
    from repro.launch import serve_loop as sl

    class Lanes:
        def dispatch(self, Y, n_live, batch_id, now):
            return sl.ImmediateHandle(lanes=[sl.LaneResult(result=None)
                                             for _ in range(n_live)])

    # six queries due at 0, a queue of two, one batch of two at a time,
    # each taking one second
    arrivals = sl.ScriptedArrivals([(0.0, [1.0])] * 6)
    policy = sl.ServePolicy(b_max=2, queue_cap=2, max_in_flight=1,
                            deadline_s=0.0)
    executor = sl.DelayedExecutor(Lanes(), lambda n, b: 1.0)
    return sl.ServeLoop(arrivals, executor, policy=policy,
                        clock=sl.VirtualClock()).run()


def test_latency_counts_the_stall():
    report = _stalled_run()
    rows = harness.ticket_rows(report.tickets)
    record = {"window": [0.0, 1.0], "tickets": rows}
    lat = sorted(harness.latencies_ms(record))
    assert lat == pytest.approx([1000.0, 1000.0, 2000.0, 2000.0,
                                 3000.0, 3000.0])
    # the program's own report times from admission, which hides the wait
    # upstream of the full queue
    assert max(report.latencies_s) == pytest.approx(2.0)
    assert sum(t.stalled for t in report.tickets) == 2


def test_a_failed_or_unconverged_query_is_infinitely_late():
    rows = [{"due": 0.0, "complete": 0.5, "ok": True, "converged": True},
            {"due": 0.1, "complete": 0.2, "ok": False, "converged": False},
            {"due": 0.2, "complete": 0.3, "ok": True, "converged": False},
            {"due": 2.0, "complete": 2.1, "ok": True, "converged": True}]
    lat = harness.latencies_ms({"window": [0.0, 1.0], "tickets": rows})
    assert lat[0] == pytest.approx(500.0)
    assert lat[1:] == [math.inf, math.inf]        # the last was not due
