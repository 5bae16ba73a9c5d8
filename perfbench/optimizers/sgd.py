"""SGD with momentum, as optax.sgd and torch.optim.SGD state it."""

from functools import partial

import jax

from perfbench.optimizers import only_tree

SLOTS = 1


def program(opt: dict):
    import optax

    return optax.sgd(opt["learning_rate"], momentum=opt["momentum"])


def first_grad(opt_state, opt: dict):
    return only_tree(opt_state, "trace"), 1.0  # after one step the trace IS the gradient


@partial(jax.jit, donate_argnums=(0, 1))
def _leaf(p, trace, g, lr, momentum):
    trace = g + momentum * trace
    return p - lr * trace, trace


def reference_leaf(p, slots, g, t: int, opt: dict):
    p, trace = _leaf(p, slots[0], g, opt["learning_rate"], opt["momentum"])
    return p, (trace,)
