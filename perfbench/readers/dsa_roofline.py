"""The kernels of the selecting attention in the traced steps
(tpunet/ops/dsa_attention.py: `dsa_index_fwd`, `dsa_attn_fwd`, `dsa_attn_dq`,
`dsa_attn_dkv`, found by name).

direction "index", "attn_fwd" or "attn_bwd": the least time the chip could
take for that kind's kernels by the configuration's shapes (the larger of
FLOPs over the peak and bytes over the memory bandwidth; `layer_dsa` of the
configuration's family under perfbench/models/ counts both: the indexer's
scores over the causal pairs, the attention over the SELECTED pairs alone,
whatever implements it), times the calls a layer a step that the trace holds
(readers/kernel_roofline.py), over the kernels' summed device time. Which
bound holds is written to the run's record. direction "share": the device
time of every `dsa_` kernel over the window's busy time. None where no such
kernel ran, or where the family counts no selecting attention."""

import importlib

from perfbench.readers import kernel_roofline


def read(ctx: dict, params: dict):
    direction = params["direction"]
    if direction == "share":
        return kernel_roofline.share(ctx, params)
    cfg, mix = ctx["cell"]["config"], ctx["cell"]["traffic"]
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    if not hasattr(family, "layer_dsa"):
        return None
    layers = cfg["num_hidden_layers"]
    need_f, need_b = family.layer_dsa(cfg, mix["batch"], mix["seq"])[direction]
    return kernel_roofline.read(ctx, params, "dsa_" + direction, layers,
                                need_f * layers, need_b * layers)
