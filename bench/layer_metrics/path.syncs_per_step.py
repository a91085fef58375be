"""Mean device-to-host reads of one lambda step
(``PathStepStats.host_syncs``: each a ``path.sync`` span in the trace)."""
from bench.layer_metrics._steps import step_mean


def read(record):
    return step_mean(record, "host_syncs")
