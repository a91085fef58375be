"""Mean share of the p features a lambda step keeps after screening (the
union over the batch): the work left to the solver."""
from bench.layer_metrics._common import live_steps, mean


def read(record):
    p = record["config"]["generator"]["params"]["p"]
    return mean(s["n_kept"] / p for s in live_steps(record))
