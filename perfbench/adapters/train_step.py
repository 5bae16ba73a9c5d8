"""A training cell on one chip, no other rank: the program's
make_train_step driven by tpunet.train.fit, in this process."""

from __future__ import annotations

from perfbench.adapters import _train


def run(cell: dict, seed: int, seconds: float, trace: bool,
        platform: str = "tpu", fault: str | None = None) -> dict:
    return _train.run_rank(cell, seed, seconds, trace, platform=platform,
                           rank=0, world=1, is_twin=False, ctl_read=None,
                           ctl_write=[], fault=fault)
