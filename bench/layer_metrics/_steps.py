"""What the readers of the program's step counters share: a counter that
the program does not record (a program without core/tracing.py) reads as
nothing."""
from bench.layer_metrics._common import live_steps, mean


def step_mean(record: dict, field: str, scale: float = 1.0):
    """Mean of one ``PathStepStats`` field over the window's live steps,
    times ``scale``; None where no step records the field."""
    steps = live_steps(record)
    if not steps or any(field not in s for s in steps):
        return None
    return mean(s[field] for s in steps) * scale
