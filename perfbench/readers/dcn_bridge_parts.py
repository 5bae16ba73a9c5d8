"""The DCN bridge from inside, a step: what the exchange costs the host
outside the native collective, cut at the program's span dcn.bridge (the
whole of tpunet/interop.py's exchange between the trainer's two programs, or
the body of a host callback), which the profiler holds on the device
operations' timeline.

  host  dcn.bridge less every dcn.bridge.collective inside it: the waits
        for the chunks' landings (stage_in), the device_put calls back
        (stage_out) and whatever else the exchange does on the host

Summed over the bridge spans whole in the traced window, over its steps. The
two stages by themselves are readers/span_sum.py's. (Until PR 25 the
exchange was a host callback inside the step's program and two more parts,
d2h and h2d, were cut at the device's host-transfer operations; those
operations are gone from the timeline and the parts with them.)

By hand, on a trace kept with PERFBENCH_KEEP_TRACE=1:
    python3 -m perfbench.readers.dcn_bridge_parts <trace dir>
prints the parts, the device's idle gaps by the innermost program span at
each gap's middle, and the clock residual of the spans that both the native
tracer and the profiler hold."""

import glob
import json
import os
import statistics
import sys

from perfbench import trace
from perfbench.readers import program_spans, span_self_time, span_sum


def host_seconds(prog: trace.Trace, lo: float, hi: float, params: dict) -> list:
    """dcn.bridge less the collectives inside it, one a bridge span whole in
    [lo, hi]. The spans' names come with the metric's file."""
    return span_self_time.self_seconds(prog, lo, hi, params["span"],
                                       [params["collective"]])


def read(ctx: dict, params: dict):
    prog = program_spans.load(ctx)
    steps = ctx["run"].get("traced_steps")
    if prog is None or not steps:
        return None
    got = host_seconds(prog, ctx["lo"], ctx["hi"], params)
    return sum(got) / steps if got else None


# -- by hand --------------------------------------------------------------------

def mirrored_offsets(xplane: str, native_dir: str) -> list:
    """Profiler start less native start, in seconds, of every root span
    (name, seq) that both files hold: the two clocks' offset, span by span."""
    from jax.profiler import ProfileData

    seen = {}
    for plane in ProfileData.from_file(xplane).planes:
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(program_spans.PREFIX):
                    seq = dict(ev.stats).get("seq")
                    if seq is not None:
                        seen[(ev.name[len(program_spans.PREFIX):], int(seq))] = ev.start_ns * 1e-9
    out = []
    for path in sorted(glob.glob(os.path.join(native_dir, "tpunet-trace-rank*.json")))[:1]:
        with open(path) as fh:
            for ev in json.load(fh):
                args = ev.get("args") or {}
                key = (ev.get("name"), args.get("seq"))
                if ev.get("ph") == "X" and "parent" not in args and key in seen:
                    out.append(seen[key] - ev["ts"] * 1e-6)
    return out


def main(trace_dir: str) -> None:
    from perfbench import harness

    params = harness.load("metrics", "dcn_bridge_host_s_per_step")["params"]
    path = trace.find_xplane(trace_dir)
    dev = trace.load(path)
    prog = trace.load(path, host_prefix=program_spans.PREFIX)
    lo, hi = trace.window_of(dev)
    rows = {"host": host_seconds(prog, lo, hi, params)}
    print(f"window {hi - lo:.6f} s, {len(rows['host'])} bridge span(s)")
    for part in ("collective", "stage_in", "stage_out"):
        rows[part] = span_sum.sums(prog, lo, hi, params["span"],
                                   f"{params['span']}.{part}")
    for key, got in rows.items():
        print(f"  {key:<10} {len(got):3d} span(s), sum {sum(got):.6f} s" +
              (f", median {statistics.median(got):.6f} s" if got else ""))
    print("device idle gaps by innermost program span:")
    for name, seconds in trace.idle_gaps(prog, lo, hi, n=20):
        print(f"  {name:<24} {seconds:.6f} s")
    off = mirrored_offsets(path, os.path.join(trace_dir, "native"))
    if off:
        mid = statistics.median(off)
        print(f"clock: {len(off)} mirrored root span(s), profiler less native "
              f"{mid:.6f} s, residual max {max(abs(x - mid) for x in off) * 1e6:.1f} us, "
              f"median {statistics.median(abs(x - mid) for x in off) * 1e6:.1f} us")
    else:
        print("clock: no span is in both files")


if __name__ == "__main__":
    main(sys.argv[1])
