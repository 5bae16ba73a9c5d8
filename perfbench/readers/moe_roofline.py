"""The grouped products of the traced steps (tpunet/ops/grouped_matmul.py:
the `moe_gmm_fwd`, `moe_gmm_dx` and `moe_tgmm_dw` kernels, found by name).

direction "fwd" or "bwd": the least time the chip could take for that
direction's kernels by the configuration's shapes (the larger of FLOPs over
the peak and bytes over the memory bandwidth; `layer_gmm` of the
configuration's family under perfbench/models/ counts both, at the rows a
uniform router sends to the held experts: an expectation, not a count),
times the calls a layer a step that the trace holds
(readers/kernel_roofline.py), over the kernels' summed device time. Which
bound holds is written to the run's record. direction "share": the device
time of all of them over the window's busy time. None where no such kernel
ran, or where the family counts no grouped product."""

import importlib

from perfbench.readers import kernel_roofline


def read(ctx: dict, params: dict):
    direction = params["direction"]
    if direction == "share":
        return kernel_roofline.share(ctx, params)
    cfg, mix = ctx["cell"]["config"], ctx["cell"]["traffic"]
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    if not hasattr(family, "layer_gmm"):
        return None
    layers = cfg["num_hidden_layers"]
    need_f, need_b = family.layer_gmm(cfg, mix["batch"] * mix["seq"])[direction]
    return kernel_roofline.read(ctx, params, "moe_" + direction, layers,
                                need_f * layers, need_b * layers)
