"""Mean solver iterations of a lambda step (the batch's most)."""
from bench.layer_metrics._common import live_steps, mean


def read(record):
    return mean(s["solver_iters"] for s in live_steps(record))
