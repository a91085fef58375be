"""Mean share of the G = p / m groups a lambda step keeps after screening
(the union over the batch), on a configuration in groups of m: the work
left to the solver. ``n_kept`` counts groups when m > 1."""
from bench.layer_metrics._common import live_steps, mean


def read(record):
    config = record["config"]
    groups = config["session"].get("groups")
    if not groups:
        return None
    G = config["generator"]["params"]["p"] // int(groups)
    return mean(s["n_kept"] / G for s in live_steps(record))
