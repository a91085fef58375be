"""What several per-layer readers share: the window's dispatches and their
live lambda steps."""


def window_dispatches(record: dict) -> list:
    t0, t_end = record["window"]
    return [d for d in record["dispatches"] if t0 <= d["t"] <= t_end]


def live_steps_of(dispatches: list) -> list:
    """Lambda steps of these dispatches that screened (a step where every
    query of the batch sits at or above its own lambda_max does not)."""
    return [s for d in dispatches for s in d["steps"] if s["x_passes"] > 0]


def live_steps(record: dict) -> list:
    """Live lambda steps of the window's dispatches."""
    return live_steps_of(window_dispatches(record))


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None
