"""The one arrival generator every traffic mix is read by.

A mix's ``arrivals`` entry is one of

* ``{"kind": "backlog", "count": M}``: M queries all due when the window
  opens, more than the window can drain; the window closes the source, so
  what is still unserved when it ends stays unserved;
* ``{"kind": "poisson", "rate": r}``: independent users at r queries/s.

A Poisson window of length s at rate r holds exactly round(r * s) arrivals
at uniform times: a Poisson process given its count. So every seed offers
the same number of queries, at other times.
"""

import numpy as np


def arrival_offsets(arrivals: dict, seconds: float, rng) -> tuple:
    """(sorted due offsets in seconds from the window's start, whether the
    window's end closes the source)."""
    kind = arrivals["kind"]
    if kind == "backlog":
        return np.zeros(int(arrivals["count"])), True
    if kind != "poisson":
        raise ValueError(f"unknown arrival kind {kind!r}")
    count = int(round(float(arrivals["rate"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, count)), False
