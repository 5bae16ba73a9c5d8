"""The program's own spans (tpunet.telemetry.span) as the profiler saw
them: TraceMes named "tpunet:<span>", on the device operations' timeline.
Shared by the readers of the bridge's parts and of a span's self time.

The run's trace lies beside the native span directory (run["native_dir"] is
<trace dir>/native); it is read a second time with the program's prefix,
once a run, and kept in ctx. A test hands a ready-made Trace in as
ctx["program_trace"]."""

import os

from perfbench import trace

PREFIX = "tpunet:"


def load(ctx: dict):
    """A Trace whose .host holds the program's spans, or None."""
    if "program_trace" not in ctx:
        found = None
        native_dir = (ctx.get("run") or {}).get("native_dir")
        if native_dir:
            try:
                path = trace.find_xplane(os.path.dirname(native_dir))
                found = trace.load(path, host_prefix=PREFIX)
            except FileNotFoundError:
                pass
        ctx["program_trace"] = found
    return ctx["program_trace"]


def named(t, name: str, lo: float, hi: float):
    """[(start, duration)] of the spans of that name that lie whole inside
    [lo, hi], in time order."""
    return sorted((s, d) for n, s, d in t.host
                  if n == name and s >= lo and s + d <= hi)


def inside(spans, start: float, duration: float) -> float:
    """Summed duration of the spans that lie inside [start, start + duration]."""
    end = start + duration
    return sum(d for s, d in spans if s >= start and s + d <= end)
