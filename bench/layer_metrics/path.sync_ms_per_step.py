"""Mean host time of one lambda step spent in device-to-host reads, in ms
(``PathStepStats.host_sync_s``: the ``path.sync`` spans, each waiting for
the device work it reads)."""
from bench.layer_metrics._steps import step_mean


def read(record):
    return step_mean(record, "host_sync_s", 1e3)
