"""The EVA kernels of the traced steps (tpunet/ops/eva_attention.py: the
`eva_local_*` and `eva_remote_*` kernels, found by name).

direction "fwd" or "bwd": the least time the chip could take for that
direction's kernels, local and remote together, by the configuration's
shapes (the larger of FLOPs over the peak and bytes over the memory
bandwidth; perfbench/models/evabyte.py counts both), over the kernels'
summed device time. Which bound holds is written to the run's record.
direction "share": the device time of all of them over the window's busy
time. None where no such kernel ran."""

from perfbench import trace
from perfbench.models import evabyte


def read(ctx: dict, params: dict):
    t, lo, hi, run, cell = ctx["trace"], ctx["lo"], ctx["hi"], ctx["run"], ctx["cell"]
    if ctx["peaks"] is None or not run.get("traced_steps"):
        return None
    spent = trace.seconds_by_name(trace.all_ops(t, lo, hi), params["pattern"])
    spent /= max(len(t.ops), 1)
    if spent <= 0:
        return None
    direction = params["direction"]
    if direction == "share":
        busy = trace.busy_seconds(t, lo, hi)
        return 100.0 * spent / busy if busy > 0 else None
    cfg, mix = cell["config"], cell["traffic"]
    layers = cfg["num_hidden_layers"]
    fwd = evabyte.attention_flops_fwd(cfg, mix["batch"], mix["seq"])
    if direction == "fwd":
        # the forward kernels run once in the forward pass and once more in
        # remat's recompute; the roofline counts what the kernels are asked for
        calls = 2 if cell.get("remat") else 1
        need_f = fwd * layers * calls
        need_b = evabyte.attention_bytes_fwd(cfg, mix["batch"], mix["seq"]) * layers * calls
    else:
        need_f = 2.5 * fwd * layers  # dq, dk, dv and the recomputed scores
        need_b = evabyte.attention_bytes_bwd(cfg, mix["batch"], mix["seq"]) * layers
    t_flops = need_f / ctx["peaks"]["flops_per_s"]
    t_bytes = need_b / ctx["peaks"]["bytes_per_s"]
    run.setdefault("roofline_bound", {})["eva_" + direction] = (
        "flops" if t_flops >= t_bytes else "bytes")
    return 100.0 * max(t_flops, t_bytes) * run["traced_steps"] / spent
