"""AdamW with decoupled weight decay, as optax.adamw states it."""

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.optimizers import only_tree

SLOTS = 2


def program(opt: dict):
    import optax

    return optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"])


def first_grad(opt_state, opt: dict):
    return only_tree(opt_state, "mu"), 1.0 / (1.0 - opt["b1"])  # mu = (1 - b1) * g


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _leaf(p, mu, nu, g, t, lr, b1, b2, eps, wd):
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    mu_hat = mu / (1 - b1 ** t)
    nu_hat = nu / (1 - b2 ** t)
    return p - lr * (mu_hat / (jnp.sqrt(nu_hat) + eps) + wd * p), mu, nu


def reference_leaf(p, slots, g, t: int, opt: dict):
    p, mu, nu = _leaf(p, slots[0], slots[1], g, float(t), opt["learning_rate"],
                      opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    return p, (mu, nu)
