"""1 less the union of device-operation intervals over the traced window,
averaged over the device planes."""

from perfbench import trace


def read(ctx: dict, params: dict):
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    if not t.ops or hi <= lo:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(t, lo, hi) / (hi - lo))
