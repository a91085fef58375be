"""Device time of collective operations per live lambda step, in ms: the
durations of every all-gather, all-reduce, reduce-scatter, all-to-all and
collective-permute (and the ``-start``/``-done`` halves of their
asynchronous forms) in the traced window, summed on each device and
averaged over the devices, over the live steps of every dispatch the trace
holds. An operation is matched by its HLO opcode in the op's text, as a
TPU trace names it (``%all-gather.3 = f32[...] all-gather(...)``). Nothing
where the trace has no device."""
import re

from bench.layer_metrics._common import live_steps_of

COLLECTIVE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")


def is_collective(op: str) -> bool:
    return COLLECTIVE.search(op) is not None


def read(record):
    device = record["device"]
    if not device or not device["n_devices"]:
        return None
    steps = live_steps_of(record["dispatches"])
    if not steps:
        return None
    total_ns = sum(dur for ops in device["ops"].values()
                   for op, _, dur, _ in ops if is_collective(op))
    return total_ns / device["n_devices"] / len(steps) / 1e6
