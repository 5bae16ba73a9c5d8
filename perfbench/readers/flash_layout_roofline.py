"""flash_roofline's quantity for a model whose layers differ in kind: the
least time the chip could take for the attention of the traced steps (the
larger of FLOPs over the peak and bytes over the memory bandwidth), the
work counted A LAYER BY ITS KIND (`layer_windows` and `attention_flops_fwd`
of the configuration's family under perfbench/models/: a full causal layer
counts all the keys before a query, a window layer those inside the
window), over the summed device time of the flash kernels. Which bound
holds is written to the run's record. None where no such kernel ran, or
where the family states no kinds of layer (readers/flash_roofline.py reads
those configurations)."""

import importlib

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
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    if not hasattr(family, "layer_windows"):
        return None
    windows = family.layer_windows(cfg)
    fwd = sum(family.attention_flops_fwd(cfg, mix["batch"], mix["seq"], w)
              for w in windows)
    if params["direction"] == "fwd":
        # the forward kernel runs once in the forward pass and once more in
        # remat's recompute; the roofline counts what the kernel is asked for
        calls = 2 if cell.get("remat") else 1
        need_f = fwd * calls
        need_b = flops.flash_bytes_fwd(cfg, mix["batch"], mix["seq"]) * len(windows) * calls
    else:
        need_f = 2.5 * fwd  # dq, dk, dv and the recomputed scores
        need_b = flops.flash_bytes_bwd(cfg, mix["batch"], mix["seq"]) * len(windows)
    t_flops = need_f / ctx["peaks"]["flops_per_s"]
    t_bytes = need_b / ctx["peaks"]["bytes_per_s"]
    run.setdefault("roofline_bound", {})["layout_" + params["direction"]] = (
        "flops" if t_flops >= t_bytes else "bytes")
    return 100.0 * max(t_flops, t_bytes) * run["traced_steps"] / spent
