"""Mean host span of one lambda step's reduced solves, in ms
(``PathStepStats.solve_time_s``: gather, solve and the syncs that end it)."""
from bench.layer_metrics._common import live_steps, mean


def read(record):
    m = mean(s["solve_time_s"] for s in live_steps(record))
    return None if m is None else m * 1e3
