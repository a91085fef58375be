"""Mean host span of one lambda step's copy-out, in ms
(``PathStepStats.copyout_time_s``: the ``path.copyout`` span, the float64
solution and the mask into the result)."""
from bench.layer_metrics._steps import step_mean


def read(record):
    return step_mean(record, "copyout_time_s", 1e3)
