"""What the kernel roofline readers share: the kernel's events in the
traced window, how many times a layer a step the kernel ran, and the share.

The calls are COUNTED, not assumed: events / (devices x traced steps x
layers x kernels_per_call), where `kernels_per_call` (the metric's file) is
how many kernel events one pass of a layer makes under the metric's
pattern. A program that runs the forward kernel again under remat reads 2,
one that keeps the kernel's outputs reads 1, and the share is the same
whenever a call takes the same time. A count that is not a whole number (a
window that cut a step, layers that differ in how often they run) gives no
share: the reader returns None and the run's notes say why."""

from perfbench import trace

# How far from a whole number a count of calls may lie: one kernel event cut
# at the window's edge among hundreds does not silence a metric, a lost step
# (a tenth of a window of ten) does.
WHOLE = 0.02


def kernel_events(ctx: dict, params: dict) -> tuple[int, float, int]:
    """(events, their summed seconds a device, devices) of the metric's
    pattern in the traced window."""
    t = ctx["trace"]
    ops = list(trace.all_ops(t, ctx["lo"], ctx["hi"]))
    devices = max(len(t.ops), 1)
    return (trace.count_by_name(ops, params["pattern"]),
            trace.seconds_by_name(ops, params["pattern"]) / devices, devices)


def calls_found(events: int, devices: int, steps: int, layers: int,
                kernels_per_call: int):
    """(calls a layer a step as found, the whole number it stands for or
    None)."""
    found = events / (devices * steps * layers * kernels_per_call)
    calls = round(found)
    return found, calls if calls >= 1 and abs(found - calls) <= WHOLE else None


def read(ctx: dict, params: dict, key: str, layers: int, need_f: float,
         need_b: float):
    """The least time the chip could take for the calls the trace holds
    (the larger of FLOPs over the peak and bytes over the memory bandwidth)
    over the kernels' summed device time. need_f, need_b: FLOPs and bytes of
    ONE pass of every layer in one step. Which bound holds, and the calls
    found, are written to the run's record under `key`."""
    run = ctx["run"]
    steps = run.get("traced_steps")
    if ctx["peaks"] is None or not steps:
        return None
    events, spent, devices = kernel_events(ctx, params)
    if spent <= 0:
        return None
    found, calls = calls_found(events, devices, steps, layers,
                               params.get("kernels_per_call", 1))
    if calls is None:
        run.setdefault("roofline_skipped", {})[key] = (
            f"{found:.4f} calls a layer a step over {steps} traced steps is no "
            "whole number: the window cut a step, or layers differ")
        return None
    run.setdefault("kernel_calls", {})[key] = calls
    t_flops = need_f * calls / ctx["peaks"]["flops_per_s"]
    t_bytes = need_b * calls / ctx["peaks"]["bytes_per_s"]
    run.setdefault("roofline_bound", {})[key] = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) * steps / spent


def share(ctx: dict, params: dict):
    """The kernels' device time over the traced window's busy time."""
    _, spent, _ = kernel_events(ctx, params)
    busy = trace.busy_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    return 100.0 * spent / busy if spent > 0 and busy > 0 else None
