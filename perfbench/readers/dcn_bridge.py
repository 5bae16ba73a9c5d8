"""Device time a step spent waiting in host transfers (the done-halves of
io_callback's send and receive: device to host, the host's work, host to
device), less the native ring's time in the same steps: what the bridge
adds around the collective. Durations only, so the two clocks need no
alignment."""

from perfbench import trace
from perfbench.readers import native_ring


def read(ctx: dict, params: dict):
    t, lo, hi, run = ctx["trace"], ctx["lo"], ctx["hi"], ctx["run"]
    if not t.ops or not run.get("traced_steps") or "trace_t0" not in run:
        return None
    waits = trace.seconds_by_name(trace.all_ops(t, lo, hi),
                                  params.get("pattern", trace.WAIT_OPS))
    waits /= len(t.ops)
    if waits <= 0:
        return None
    ring = native_ring.ring_seconds(run["native_dir"], run["trace_t0"],
                                    run["trace_t1"]) or 0.0
    return max(waits - ring, 0.0) / run["traced_steps"]
