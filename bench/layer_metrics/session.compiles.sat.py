"""Programs compiled, or loaded from the persistent cache, inside the window
of a backlog cell (JAX's backend-compile events fire for both)."""


def read(record):
    return record["compiles_in_window"]
