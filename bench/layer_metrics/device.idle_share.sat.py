"""Share of the traced window in which no operation ran on the device, in %
(a backlog cell)."""


def read(record):
    device = record["device"]
    if not device or not device["n_devices"]:
        return None
    return 100.0 * (1.0 - device["busy_s"] / device["window_s"])
