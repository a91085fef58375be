"""Mean host time of a dispatch outside its lambda steps, in ms: the
dispatch's span less the sum of its ``PathStepStats.step_time_s`` (the
prologue, the lanes, the executor), over the window's dispatches."""
from bench.layer_metrics._common import mean, window_dispatches


def read(record):
    out = []
    for d in window_dispatches(record):
        if not d["steps"]:
            continue
        if any("step_time_s" not in s for s in d["steps"]):
            return None
        inside = sum(s["step_time_s"] for s in d["steps"])
        out.append((d["t_done"] - d["t"] - inside) * 1e3)
    return mean(out)
