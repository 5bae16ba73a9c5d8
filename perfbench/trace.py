"""From a profiler trace (.xplane.pb) to numbers. jax.profiler.ProfileData
reads the file with nothing but JAX.

What a device is called, which of its lines carries the operations, and how
kernels and programs are named differ between backends; the patterns are
arguments, and the per-layer metrics keep theirs in their own files.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
MODULES_LINE = r"^XLA Modules$"
# An operation that only waits for the host: the done-half of a host
# transfer (io_callback's send and receive). The device runs nothing while
# it is open, so it counts as idle, not busy.
WAIT_OPS = r"(send|recv)-done\(.*is_host_transfer=true"


@dataclass
class Trace:
    """Times in seconds from the trace's own zero. ops and modules:
    {plane: [(name, start, duration)]} of the device planes; host:
    [(name, start, duration)] of the benchmark's TraceAnnotations."""

    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    host: list = field(default_factory=list)
    inventory: dict = field(default_factory=dict)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE,
         modules_line: str = MODULES_LINE, host_prefix: str = "pb:") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    t = Trace()
    for plane in data.planes:
        lines = list(plane.lines)
        t.inventory[plane.name] = [ln.name for ln in lines]
        is_device = bool(re.search(device_plane, plane.name))
        for ln in lines:
            dest = None
            if is_device and re.search(ops_line, ln.name):
                dest = t.ops
            elif is_device and re.search(modules_line, ln.name):
                dest = t.modules
            for ev in ln.events:
                if ev.name.startswith(host_prefix):
                    t.host.append((ev.name[len(host_prefix):], ev.start_ns * 1e-9,
                                   ev.duration_ns * 1e-9))
                elif dest is not None:
                    dest.setdefault(plane.name, []).append(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return t


def union_seconds(intervals) -> float:
    """Length of the union of (start, duration) intervals."""
    total, end = 0.0, float("-inf")
    for s, d in sorted(intervals):
        e = s + d
        if s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def clip(events, lo: float, hi: float):
    """Events cut to [lo, hi]."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_seconds(t: Trace, lo: float, hi: float, wait_ops: str = WAIT_OPS) -> float:
    """Seconds in [lo, hi] in which an operation ran on the device,
    averaged over the device planes. Operations that only wait for the
    host (WAIT_OPS) are not work."""
    if not t.ops:
        return 0.0
    rx = re.compile(wait_ops)
    per = [union_seconds((s, d) for n, s, d in clip(ev, lo, hi) if not rx.search(n))
           for ev in t.ops.values()]
    return sum(per) / len(per)


def seconds_by_name(events, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(d for name, _, d in events if rx.search(name))


def count_by_name(events, pattern: str) -> int:
    """Events whose name matches: how often a kernel ran, where
    seconds_by_name says for how long."""
    rx = re.compile(pattern)
    return sum(1 for name, _, _ in events if rx.search(name))


def all_ops(t: Trace, lo: float, hi: float):
    for ev in t.ops.values():
        yield from clip(ev, lo, hi)


def top_ops(t: Trace, lo: float, hi: float, n: int = 10):
    total: dict = {}
    for name, _, d in all_ops(t, lo, hi):
        name = name[:160]  # an op's name is its whole HLO line
        total[name] = total.get(name, 0.0) + d
    n_dev = max(len(t.ops), 1)
    return [[k, v / n_dev] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(t: Trace, lo: float, hi: float, n: int = 10):
    """The longest idle gaps of the first device in [lo, hi], summed by what
    the host was doing at the gap's middle (the innermost benchmark
    annotation there, or "unannotated")."""
    if not t.ops:
        return []
    rx = re.compile(WAIT_OPS)
    events = sorted((s, d) for n, s, d in clip(next(iter(t.ops.values())), lo, hi)
                    if not rx.search(n))
    gaps, end = [], lo
    for s, d in events:
        if s > end:
            gaps.append((end, s - end))
        end = max(end, s + d)
    if hi > end:
        gaps.append((end, hi - end))
    by: dict = {}
    for s, d in gaps:
        mid = s + d / 2
        inside = [(hd, name) for name, hs, hd in t.host if hs <= mid <= hs + hd]
        name = min(inside)[1] if inside else "unannotated"
        by[name] = by.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def window_of(t: Trace, name: str = "window") -> tuple[float, float]:
    """[start, end] of the benchmark's own annotation around the traced
    window; the whole span of device events where it is missing."""
    for n, s, d in t.host:
        if n == name:
            return s, s + d
    starts = [s for ev in t.ops.values() for _, s, _ in ev]
    ends = [s + d for ev in t.ops.values() for _, s, d in ev]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)
