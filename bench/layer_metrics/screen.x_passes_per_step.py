"""Mean passes over X of one live lambda step's screen
(``PathStepStats.x_passes``): 1 where one pass screens the whole batch, B
where the batch's queries are screened one by one."""
from bench.layer_metrics._common import live_steps, mean


def read(record):
    return mean(s["x_passes"] for s in live_steps(record))
