"""Seconds a step the chip rank's native collective phases were running:
the union of the collective phase spans (those that carry a coll_seq) that
tpunet.telemetry.profile wrote for the traced window, over its steps."""

import glob
import json
import os

from perfbench import trace


def ring_seconds(native_dir: str, t0: float, t1: float):
    """The native tracer stamps CLOCK_MONOTONIC microseconds, the clock of
    time.perf_counter(), so its spans are cut to the traced window
    [t0, t1] given in perf_counter seconds."""
    files = sorted(glob.glob(os.path.join(native_dir, "tpunet-trace-rank*.json")))
    if not files:
        return None
    spans = []
    with open(files[0]) as fh:  # the chip rank's own file
        for ev in json.load(fh):
            if ev.get("ph") == "X" and "coll_seq" in ev.get("args", {}):
                spans.append(("", ev["ts"] * 1e-6, ev["dur"] * 1e-6))
    spans = [(s, d) for _, s, d in trace.clip(spans, t0, t1)]
    return trace.union_seconds(spans) if spans else None


def read(ctx: dict, params: dict):
    run = ctx["run"]
    if "trace_t0" not in run:
        return None
    total = ring_seconds(run["native_dir"], run["trace_t0"], run["trace_t1"])
    if total is None or not run.get("traced_steps"):
        return None
    return total / run["traced_steps"]
