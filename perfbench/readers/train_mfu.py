"""Model FLOPs of forward and backward per step, by the configuration's
shapes (`train_flops` of perfbench/models/<family>.py; recompute not
counted), over the whole window's time per step x the chip's peak."""

import importlib


def step_flops(cfg: dict, mix: dict) -> float:
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    return family.train_flops(cfg, mix)


def read(ctx: dict, params: dict):
    cell, run = ctx["cell"], ctx["run"]
    if not run.get("steps") or ctx["peaks"] is None:
        return None
    per_chip = step_flops(cell["config"], cell["traffic"])
    return 100.0 * per_chip / (run["step_s"] * ctx["peaks"]["flops_per_s"])
