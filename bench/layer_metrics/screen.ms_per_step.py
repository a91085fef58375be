"""Mean host span of one lambda step's screen, in ms
(``PathStepStats.screen_time_s``: it ends at the mask's copy to the host,
so it holds the dispatch, the kernel and the sync)."""
from bench.layer_metrics._common import live_steps, mean


def read(record):
    m = mean(s["screen_time_s"] for s in live_steps(record))
    return None if m is None else m * 1e3
