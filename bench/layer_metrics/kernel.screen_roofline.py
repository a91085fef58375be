"""Share of the HBM roofline that the screening matvec kernel
(``_matvec_kernel``) reaches, in %: the bytes one call must move at the
cell's shapes and padded batch, over the mean device time of its trace
events times the chip's HBM peak. On a TPU the call shows as the custom
call ``%screen_matvec.<i> = ... custom-call(...)``, named after the jitted
function that wraps the kernel. Nothing when the trace has no such event
or the chip has no peaks."""
from bench import roofline, trace
from bench.layer_metrics._common import window_dispatches

KERNEL = "screen_matvec"


def read(record):
    device = record["device"]
    if not device or not device.get("peaks"):
        return None
    times = trace.custom_call_times(device["ops"], KERNEL)
    batches = {d["padded_b"] for d in window_dispatches(record)}
    if not times or len(batches) != 1:
        return None
    params = record["config"]["generator"]["params"]
    moved = roofline.matvec_bytes(params["n"], params["p"], batches.pop())
    least = moved / device["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(times) / len(times))
