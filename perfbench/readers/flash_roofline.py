"""The least time the chip could take for the attention of the traced
steps, by the configuration's shapes (the larger of FLOPs over the peak and
bytes over the memory bandwidth), over the summed device time of the flash
kernels' events; the calls a layer a step are counted in the trace
(readers/kernel_roofline.py). The work is counted A LAYER BY ITS KIND where
the configuration's family under perfbench/models/ states kinds
(`layer_windows(cfg)` and `attention_flops_fwd(cfg, batch, seq, window)`: a
full causal layer counts all the keys before a query, a window layer those
inside the window), else every layer under `cfg.get("sliding_window")`.
Which bound holds is written to the run's record."""

import importlib

from perfbench import flops
from perfbench.readers import kernel_roofline


def attention_fwd(cfg: dict, batch: int, seq: int) -> float:
    """Useful FLOPs of one forward pass of every layer's attention."""
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    if hasattr(family, "layer_windows"):
        return sum(family.attention_flops_fwd(cfg, batch, seq, w)
                   for w in family.layer_windows(cfg))
    return flops.attention_flops_fwd(cfg, batch, seq) * cfg["num_hidden_layers"]


def read(ctx: dict, params: dict):
    cfg, mix = ctx["cell"]["config"], ctx["cell"]["traffic"]
    layers, b, s = cfg["num_hidden_layers"], mix["batch"], mix["seq"]
    fwd = attention_fwd(cfg, b, s)
    if params["direction"] == "fwd":
        need = fwd, flops.flash_bytes_fwd(cfg, b, s) * layers
    else:  # dq, dk, dv and the recomputed scores
        need = 2.5 * fwd, flops.flash_bytes_bwd(cfg, b, s) * layers
    return kernel_roofline.read(ctx, params, "flash_" + params["direction"], layers, *need)
