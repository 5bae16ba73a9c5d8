"""From a configuration file to the program's own objects, and the check
that the program's parameter tree is the one the reference's spec states."""

from __future__ import annotations

import importlib

from perfbench import weights


def reference(cfg: dict):
    return importlib.import_module(f"perfbench.references.{cfg['reference']}")


def build(cfg: dict, cell: dict):
    """The program's model for this configuration, through its normal
    constructor, by the configuration's family."""
    return importlib.import_module(f"perfbench.models.{cfg['family']}").build(cfg, cell)


def program_shapes(model, sample) -> dict:
    """The program's own parameter tree, shapes only (nothing is made)."""
    import jax

    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), sample)
    return _plain(tree["params"])


def _plain(tree):
    return {k: _plain(v) if hasattr(v, "items") else v for k, v in tree.items()}


def check_spec(shapes: dict, spec: dict) -> None:
    prog = {p: tuple(s.shape) for p, s in weights.flatten(shapes).items()}
    ref = {p: tuple(shape) for p, (shape, _) in spec.items()}
    if prog != ref:
        diff = sorted(set(prog.items()) ^ set(ref.items()))[:6]
        raise SystemExit(f"the program's parameter tree is not the "
                         f"reference's spec; first differences: {diff}")
