"""Mean host span of one lambda step's column gathers, in ms
(``PathStepStats.gather_time_s``: the ``path.gather`` spans, index upload,
bucket columns and warm start)."""
from bench.layer_metrics._steps import step_mean


def read(record):
    return step_mean(record, "gather_time_s", 1e3)
