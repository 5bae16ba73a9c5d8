"""The EVA kernels of the traced steps (tpunet/ops/eva_attention.py: the
`eva_local_*` and `eva_remote_*` kernels, found by name).

direction "fwd" or "bwd": the least time the chip could take for that
direction's kernels, local and remote together, by the configuration's
shapes (the larger of FLOPs over the peak and bytes over the memory
bandwidth; perfbench/models/evabyte.py counts both), times the calls a
layer a step that the trace holds (readers/kernel_roofline.py), over the
kernels' summed device time. Which bound holds is written to the run's
record. direction "share": the device time of all of them over the window's
busy time. None where no such kernel ran."""

from perfbench.models import evabyte
from perfbench.readers import kernel_roofline


def read(ctx: dict, params: dict):
    direction = params["direction"]
    if direction == "share":
        return kernel_roofline.share(ctx, params)
    cfg, mix = ctx["cell"]["config"], ctx["cell"]["traffic"]
    layers, b, s = cfg["num_hidden_layers"], mix["batch"], mix["seq"]
    fwd = evabyte.attention_flops_fwd(cfg, b, s) * layers
    if direction == "fwd":
        need = fwd, evabyte.attention_bytes_fwd(cfg, b, s) * layers
    else:  # dq, dk, dv and the recomputed scores
        need = 2.5 * fwd, evabyte.attention_bytes_bwd(cfg, b, s) * layers
    return kernel_roofline.read(ctx, params, "eva_" + direction, layers, *need)
