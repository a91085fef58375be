"""Reduce a profiler trace to the device numbers the benchmark reports.

The trace is read into plain event records, so that the reduction can be
checked on a small recorded trace: ``(plane, line, name, start_ns, dur_ns,
meta)``, ``meta`` the event's string statistics joined (on a TPU they hold
the operation's source path, such as ``jit(screen_matvec)/pallas_call``).
Device planes are named ``/device:<KIND>:<i>``; on a TPU their ``XLA Ops``
line holds one event per operation that ran. Busy time is the union of
those intervals (overlapping operations count once), averaged over the
devices; a kernel's device time is the sum of the durations of its events.
"""

import collections
import glob
import os

OPS_LINE = "XLA Ops"


def read_xplane(log_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``log_dir`` as a tuple
    (plane, line, name, start_ns, dur_ns, meta)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                meta = " ".join(str(v) for _, v in ev.stats
                                if isinstance(v, str)) if device else ""
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns), meta))
    return out


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith(
        "/device:CUSTOM")


def device_ops(events) -> dict:
    """{device plane: [(name, start_ns, dur_ns, meta)]} of the operations
    that ran on each device (its ``XLA Ops`` line)."""
    out = collections.defaultdict(list)
    for plane, line, name, start, dur, meta in events:
        if is_device_plane(plane) and line == OPS_LINE:
            out[plane].append((name, start, dur, meta))
    return dict(out)


def union_ns(intervals) -> float:
    """Length of the union of [start, start + dur) intervals."""
    total = 0.0
    end = None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_s(ops_by_device: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not ops_by_device:
        return 0.0
    return sum(union_ns((s, d) for _, s, d, _ in ops) for ops in
               ops_by_device.values()) / len(ops_by_device) / 1e9


def custom_call_times(ops_by_device: dict, name: str) -> list:
    """Device seconds of every custom call (a kernel) named ``name``: on a
    TPU the op reads ``%<name>.<i> = <shape> custom-call(...)``."""
    return [dur / 1e9 for ops in ops_by_device.values()
            for op, _, dur, _ in ops
            if op.startswith(f"%{name}.") and "custom-call" in op]


def idle_gaps(ops_by_device: dict, host_events, top: int = 10) -> list:
    """The longest gaps between device operations on the first device, each
    named by what the host was doing: the host span that covers most of the
    gap among those no longer than ten gaps (so that a whole dispatch does
    not name every gap inside it), else the longest covering span."""
    if not ops_by_device:
        return []
    ops = sorted((s, s + d) for _, s, d, _ in
                 ops_by_device[sorted(ops_by_device)[0]])
    gaps = []
    end = ops[0][1]
    for start, stop in ops[1:]:
        if start > end:
            gaps.append((start - end, end, start))
        end = max(end, stop)
    gaps.sort(reverse=True)
    named = []
    for length, g0, g1 in gaps[:top]:
        best, best_key = "unattributed", None
        for _, _, name, start, dur, _ in host_events:
            cover = min(g1, start + dur) - max(g0, start)
            if cover <= 0:
                continue
            key = (dur <= 10 * length, cover, -dur)
            if best_key is None or key > best_key:
                best, best_key = name, key
        named.append([best, length / 1e9])
    return named


def top_ops(ops_by_device: dict, top: int = 10, width: int = 160) -> list:
    """[[name, seconds]] of the operations that took most device time,
    averaged over the devices (names cut to ``width`` characters)."""
    total = collections.Counter()
    for ops in ops_by_device.values():
        for name, _, dur, _ in ops:
            total[name[:width]] += dur / 1e9 / len(ops_by_device)
    return [[name, sec] for name, sec in total.most_common(top)]


def reduce(events) -> dict:
    """The device record one traced window gives to the per-layer metrics."""
    ops = device_ops(events)
    host = [e for e in events if e[0].startswith("/host:")]
    return {
        "n_devices": len(ops),
        "busy_s": busy_s(ops),
        "ops": ops,
        "device_ops": top_ops(ops),
        "idle_gaps": idle_gaps(ops, host),
    }
