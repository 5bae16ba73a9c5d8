"""The kernels of one kind of block of a model whose blocks differ in kind
(a `layers_of(cfg, kind)` in its family under perfbench/models/: Nemotron-H's
"M" Mamba mixers and "E" expert layers, the MTP module's blocks included).

direction "fwd" or "bwd": the least time the chip could take for that
direction's kernels by the configuration's shapes (the larger of FLOPs over
the peak and bytes over the memory bandwidth; the family's function named by
`counter`, called (cfg, batch, seq), counts both for one block in one pass),
times the calls a block a step that the trace holds
(readers/kernel_roofline.py, over the blocks of `kind` alone), over the
kernels' summed device time. direction "share": the device time of the
pattern's kernels over the window's busy time. None where no such kernel
ran, or where the family has no such counter."""

import importlib

from perfbench.readers import kernel_roofline


def read(ctx: dict, params: dict):
    direction = params["direction"]
    if direction == "share":
        return kernel_roofline.share(ctx, params)
    cfg, mix = ctx["cell"]["config"], ctx["cell"]["traffic"]
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    counter = getattr(family, params["counter"], None)
    if counter is None or not hasattr(family, "layers_of"):
        return None
    layers = family.layers_of(cfg, params["kind"])
    need_f, need_b = counter(cfg, mix["batch"], mix["seq"])[direction]
    return kernel_roofline.read(ctx, params, params["key"], layers,
                                need_f * layers, need_b * layers)
