"""The grouped products of the traced steps (tpunet/ops/grouped_matmul.py:
the `moe_gmm_fwd`, `moe_gmm_dx` and `moe_tgmm_dw` kernels, found by name).

direction "fwd" or "bwd": the least time the chip could take for that
direction's kernels by the configuration's shapes (the larger of FLOPs over
the peak and bytes over the memory bandwidth; `layer_gmm` of the
configuration's family under perfbench/models/ counts both, at the rows a
uniform router sends to the held experts: an expectation, not a count),
over the kernels' summed device time; the forward counted twice under
remat. Which bound holds is written to the run's record. direction "share":
the device time of all of them over the window's busy time. None where no
such kernel ran, or where the family counts no grouped product."""

import importlib

from perfbench import trace


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
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    if not hasattr(family, "layer_gmm"):
        return None
    need_f, need_b = family.layer_gmm(cfg, mix["batch"] * mix["seq"])[direction]
    # the forward kernels run once in the forward pass and once more in
    # remat's recompute; the roofline counts what the kernels are asked for
    calls = 2 if direction == "fwd" and cell.get("remat") else 1
    calls *= cfg["num_hidden_layers"]
    t_flops = need_f * calls / ctx["peaks"]["flops_per_s"]
    t_bytes = need_b * calls / ctx["peaks"]["bytes_per_s"]
    run.setdefault("roofline_bound", {})["moe_" + direction] = (
        "flops" if t_flops >= t_bytes else "bytes")
    return 100.0 * max(t_flops, t_bytes) * run["traced_steps"] / spent
