"""Production mesh builders (a FUNCTION, not a module-level constant, so
importing this module never touches jax device state)."""

from __future__ import annotations

from repro.core.distributed import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=("data","model") single pod; (2,16,16)=("pod","data","model")
    for the 2-pod / 512-chip dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 16):
    """Elastic helper: best (data, model) mesh for a surviving device count."""
    model = min(model_parallel, devices)
    while devices % model:
        model //= 2
    return make_mesh((devices // model, model), ("data", "model"))
