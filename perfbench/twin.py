"""The shape twin: a rank of a data-parallel job that has no chip.

A flax module whose parameter tree has another model's exact names, shapes
and dtypes, and whose loss touches every leaf once, cheaply. Run through the
program's own make_train_step it issues whatever collectives the trainer
issues for the real model, at negligible host compute.

Its "images" are a flat dict {path: array} shaped like the parameters. The
logit is z = sum <param, input> less its own value (stop_gradient), so it
is always 0 and its gradient is the input: under the trainer's softmax
cross-entropy over [z, 0] with label 0, d loss / d z = -1/2 exactly,
whatever the parameters are. The twin's gradient is therefore -input / 2, a
function of the seed alone, and a plain reference can form the same mean.

The input is integer arithmetic turned into floats, so a CPU rank and a
reference on the chip make the same values bit for bit.
"""

from __future__ import annotations

import math
import zlib

import flax.linen as nn
import jax
import jax.numpy as jnp


def pattern(seed: int, path: str, shape, grad_norm: float):
    """The twin's input for one leaf: values in [-1/2, 1/2) from a
    multiplicative hash of the element's index, scaled so that the leaf's
    gradient, -input / 2, has the norm `grad_norm`."""
    n = math.prod(shape)
    phase = (zlib.crc32(path.encode()) ^ (int(seed) & 0xFFFFFFFF)) & 0xFFFFFFFF
    i = jnp.arange(n, dtype=jnp.uint32)
    u = ((i * jnp.uint32(2654435761) + jnp.uint32(phase)) >> 8).astype(jnp.float32)
    u = u / float(1 << 24) - 0.5
    return (u * (2.0 * grad_norm / math.sqrt(n / 12.0))).reshape(shape)


def grad_leaf(seed: int, path: str, shape, grad_norm: float):
    """What the twin contributes to the gradient sum for this leaf."""
    return -0.5 * pattern(seed, path, shape, grad_norm)


class _Node(nn.Module):
    """One level of the tree. children: ((name, shape | children, dtype), ...)"""

    children: tuple
    prefix: str = ""

    @nn.compact
    def __call__(self, inputs: dict):
        z = jnp.zeros((), jnp.float32)
        for name, what, dtype in self.children:
            path = f"{self.prefix}/{name}" if self.prefix else name
            if dtype is None:  # a subtree
                z = z + _Node(what, path, name=name)(inputs)
            else:
                p = self.param(name, nn.initializers.zeros, what, jnp.dtype(dtype))
                z = z + jnp.vdot(p.astype(jnp.float32), inputs[path])
        return z


class Twin(nn.Module):
    children: tuple

    @nn.compact
    def __call__(self, inputs: dict, train: bool = False):
        del train
        z = _Node(self.children, name="tree")(inputs)
        z = z - jax.lax.stop_gradient(z)
        return jnp.stack([z, jnp.zeros_like(z)])[None]  # (1, 2) logits


def children_of(shapes: dict) -> tuple:
    """Nested {name: ShapeDtypeStruct | dict} -> Twin's hashable spec."""
    return tuple(
        (k, children_of(v), None) if isinstance(v, dict)
        else (k, tuple(v.shape), jnp.dtype(v.dtype).name)
        for k, v in shapes.items())
