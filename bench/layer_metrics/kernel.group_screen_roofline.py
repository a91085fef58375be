"""Share of the HBM roofline that one group screen reaches, in %: the bytes
one call must move at the cell's shapes and padded batch, over the mean
device time of a call times the chip's HBM peak. A call is the screening
matvec kernel's custom call (``%screen_matvec.<i> = f32[B,p]
custom-call(...)``) and the per-group reduction that follows it on the
device: the operations that read the kernel's output, and those that read
theirs (on a v5e a slice and square, the reshape to (B, G, m), the sum and
the square root), or that carry the device scope ``group_reduce`` in their
source path where the trace gives one. Nothing when the kernel's calls do
not hold the dispatch's whole batch (a program that screens its queries
one by one), no call is followed by its reduction, the configuration has
no groups, or the chip has no peaks."""
import re

from bench.layer_metrics._common import window_dispatches

KERNEL = "screen_matvec"
SCOPE = "group_reduce"
KERNEL_CALL = re.compile(rf"^(%{KERNEL}\.\d+) = \w+\[(\d+),.*custom-call\(")
NAME = re.compile(r"%[\w.\-]+")


def group_screen_bytes(n: int, p: int, m: int, batch: int,
                       itemsize: int = 4) -> int:
    """HBM bytes of one group screen: the kernel's pass (X read once, the B
    centres read, the (B, p) dots written), then the reduction (the dots
    read, the (B, G) scores written)."""
    kernel = n * p + batch * n + batch * p
    reduction = batch * p + batch * (p // m)
    return itemsize * (kernel + reduction)


def screen_calls(ops) -> list:
    """[(rows, kernel seconds, reduction seconds)] of one device's group
    screens, from its operations in time order: each kernel call, then the
    unbroken run of operations that consume it or one another."""
    calls = []
    names = None
    for op, _, dur, meta in sorted(ops, key=lambda o: o[1]):
        head, _, operands = op.partition(" = ")
        call = KERNEL_CALL.match(op)
        if call:
            names = {call.group(1)}
            calls.append([int(call.group(2)), dur / 1e9, 0.0])
        elif names is not None and (f"/{SCOPE}/" in meta
                                    or names & set(NAME.findall(operands))):
            names.add(head)
            calls[-1][2] += dur / 1e9
        else:
            names = None
    return calls


def read(record):
    device = record["device"]
    groups = record["config"]["session"].get("groups")
    if not device or not device.get("peaks") or not groups:
        return None
    calls = [c for ops in device["ops"].values() for c in screen_calls(ops)]
    batches = {d["padded_b"] for d in window_dispatches(record)}
    if (not calls or len(batches) != 1 or {c[0] for c in calls} != batches
            or not all(c[2] > 0 for c in calls)):
        return None
    params = record["config"]["generator"]["params"]
    moved = group_screen_bytes(params["n"], params["p"], int(groups),
                               batches.pop())
    least = moved / device["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(k + r for _, k, r in calls) / len(calls))
