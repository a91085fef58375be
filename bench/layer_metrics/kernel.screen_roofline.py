"""Share of the HBM roofline that the screening matvec kernel
(``_matvec_kernel``) reaches, in %: the bytes one call must move at the
cell's shapes and padded batch, over the mean device time of its trace
events times the chip's HBM peak. On a TPU the call shows as the custom
call ``%screen_matvec.<i> = ... custom-call(...)``, named after the jitted
function that wraps the kernel. On a mesh each device calls the kernel on
its own block: the batch split over the ``query`` axis where its size
divides the batch (else whole, as the program places it), the columns
split over the other axes. Nothing when the trace has no such event or the
chip has no peaks."""
import math

from bench import roofline, trace
from bench.layer_metrics._common import window_dispatches

KERNEL = "screen_matvec"
QUERY_AXIS = "query"


def mesh_split(session: dict) -> tuple:
    """(devices along the query axis, devices along the others) of the
    configuration's mesh; (1, 1) without one."""
    mesh = session.get("mesh")
    if not mesh:
        return 1, 1
    sizes = dict(zip(mesh["axes"], mesh["shape"]))
    query = sizes.pop(QUERY_AXIS, 1)
    return query, math.prod(sizes.values())


def read(record):
    device = record["device"]
    if not device or not device.get("peaks"):
        return None
    times = trace.custom_call_times(device["ops"], KERNEL)
    batches = {d["padded_b"] for d in window_dispatches(record)}
    if not times or len(batches) != 1:
        return None
    params = record["config"]["generator"]["params"]
    query, feature = mesh_split(record["config"]["session"])
    batch = batches.pop()
    if batch % query == 0:
        batch //= query
    moved = roofline.matvec_bytes(params["n"], -(-params["p"] // feature),
                                  batch)
    least = moved / device["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(times) / len(times))
