"""One module an optimizer, found by the `name` a cell's file states:
`program(opt)` is the transformation handed to the program's
make_train_step, `first_grad(opt_state, opt)` the tree and the factor that together are
the first gradient as the optimizer got it, read from its state after one
step (no copy of the tree is made), and `SLOTS` with
`reference_leaf(p, slots, g, t, opt)` the plain reference's own update of
one leaf (its moments start at zero)."""

import importlib


def find(opt: dict):
    return importlib.import_module(f"perfbench.optimizers.{opt['name']}")


def only_tree(opt_state, attr: str):
    """The one subtree of an optax state that has the attribute `attr`."""
    import jax

    found = [getattr(s, attr) for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, attr)) if hasattr(s, attr)]
    if len(found) != 1:
        raise SystemExit(f"the optimizer state holds {len(found)} '{attr}' trees")
    return found[0]
