"""The least time the chip could take for the attention of the traced
steps, by the configuration's shapes (the larger of FLOPs over the peak and
bytes over the memory bandwidth), over the summed device time of the
kernel's events. Which bound holds is written to the run's record."""

from perfbench import flops, trace


def read(ctx: dict, params: dict):
    t, lo, hi, run, cell = ctx["trace"], ctx["lo"], ctx["hi"], ctx["run"], ctx["cell"]
    if ctx["peaks"] is None or not run.get("traced_steps"):
        return None
    spent = trace.seconds_by_name(trace.all_ops(t, lo, hi), params["pattern"])
    spent /= max(len(t.ops), 1)
    if spent <= 0:
        return None
    cfg, mix = cell["config"], cell["traffic"]
    layers = cfg["num_hidden_layers"]
    fwd = flops.attention_flops_fwd(cfg, mix["batch"], mix["seq"])
    if params["direction"] == "fwd":
        # the forward kernel runs once in the forward pass and once more in
        # remat's recompute; the roofline counts what the kernel is asked for
        calls = 2 if cell.get("remat") else 1
        need_f = fwd * layers * calls
        need_b = flops.flash_bytes_fwd(cfg, mix["batch"], mix["seq"]) * layers * calls
    else:
        need_f = 2.5 * fwd * layers  # dq, dk, dv and the recomputed scores
        need_b = flops.flash_bytes_bwd(cfg, mix["batch"], mix["seq"]) * layers
    t_flops = need_f / ctx["peaks"]["flops_per_s"]
    t_bytes = need_b / ctx["peaks"]["bytes_per_s"]
    run.setdefault("roofline_bound", {})[params["direction"]] = (
        "flops" if t_flops >= t_bytes else "bytes")
    return 100.0 * max(t_flops, t_bytes) * run["traced_steps"] / spent
