"""The DCN bridge from inside, a step: the three parts of the host-transfer
wait around the native collective, cut at the program's span dcn.bridge
(the whole body of tpunet/interop.py's host callback), which the profiler
holds on the device operations' timeline.

  d2h   from the start of the device's host transfer (the first send,
        send-done, recv or recv-done with is_host_transfer since the
        callback before) to the entry of dcn.bridge: the copy off the chip
        and all that the runtime and JAX do before the program's code runs
  host  dcn.bridge less dcn.bridge.collective: the program's own staging
  h2d   from the return of dcn.bridge to the end of the device's recv-done
        that contains it: the result's way back

Each summed over the bridge spans of the traced window, over its steps.

By hand, on a trace kept with PERFBENCH_KEEP_TRACE=1:
    python3 -m perfbench.readers.dcn_bridge_parts <trace dir>
prints the parts, the device's idle gaps by the innermost program span at
each gap's middle, and the clock residual of the spans that both the native
tracer and the profiler hold."""

import glob
import json
import os
import re
import statistics
import sys

from perfbench import trace
from perfbench.readers import program_spans


def split(dev: trace.Trace, prog: trace.Trace, lo: float, hi: float,
          params: dict) -> list:
    """One {"d2h", "host", "h2d", "collective"} a bridge span; a part the
    trace cannot give is None. The spans' names and the pattern of the
    device's host-transfer operations come with the metric's file."""
    bridges = program_spans.named(prog, params["span"], lo, hi)
    inner = program_spans.named(prog, params["collective"], lo, hi)
    transfer = re.compile(params["transfer"])
    wait = re.compile(params.get("wait", trace.WAIT_OPS))
    per_plane = (sorted((s, s + d, bool(wait.search(n))) for n, s, d in ev
                        if transfer.search(n)) for ev in dev.ops.values())
    moves = next((m for m in per_plane if m), [])
    out, before = [], lo
    for s, d in bridges:
        coll = program_spans.inside(inner, s, d)
        starts = [a for a, _, _ in moves if before <= a <= s]
        ends = [b for a, b, is_wait in moves if is_wait and a <= s + d <= b]
        out.append({"d2h": s - min(starts) if starts else None,
                    "host": d - coll, "collective": coll,
                    "h2d": max(ends) - (s + d) if ends else None})
        before = s + d
    return out


def read(ctx: dict, params: dict):
    prog = program_spans.load(ctx)
    steps = ctx["run"].get("traced_steps")
    if prog is None or not steps:
        return None
    got = [p[params["part"]] for p in split(ctx["trace"], prog, ctx["lo"], ctx["hi"], params)]
    got = [x for x in got if x is not None]
    if not got:
        return None
    return sum(got) / steps


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

    params = harness.load("metrics", "dcn_bridge_d2h_s_per_step")["params"]
    path = trace.find_xplane(trace_dir)
    dev = trace.load(path)
    prog = trace.load(path, host_prefix=program_spans.PREFIX)
    lo, hi = trace.window_of(dev)
    parts = split(dev, prog, lo, hi, params)
    print(f"window {hi - lo:.6f} s, {len(parts)} bridge span(s)")
    for key in ("d2h", "host", "collective", "h2d"):
        got = [p[key] for p in parts if p[key] is not None]
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
