"""VGG16, configuration D of Simonyan and Zisserman (arXiv:1409.1556):
thirteen 3x3 convolutions with ReLU in five blocks, a 2x2 max-pool after
each block, then 4096, 4096 and 1000-way dense layers; NHWC images.
Dropout in the classifier is off, as the configuration file states (the
repository's own synthetic benchmark builds the model so). Parameter paths
are the program's: conv0..conv12, fc1, fc2, head, each kernel and bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.models.vgg import layers as vgg_layers
from perfbench.references.precision import HIGHEST, matmul, operand, store


def _names(c: dict):
    conv = 0
    dense = iter(("fc1", "fc2", "head"))
    for kind, i, o, _ in vgg_layers(c):
        if kind == "conv":
            yield f"conv{conv}", kind, i, o
            conv += 1
        else:
            yield next(dense), kind, i, o


def param_spec(c: dict) -> dict:
    spec = {}
    for name, kind, i, o in _names(c):
        # torchvision's VGG, which the source's script trains: convolutions
        # He-normal over the fan-out, dense layers normal(0, 0.01), biases 0
        shape = (3, 3, i, o) if kind == "conv" else (i, o)
        std = math.sqrt(2.0 / (9 * o)) if kind == "conv" else 0.01
        spec[f"{name}/kernel"] = (shape, std)
        spec[f"{name}/bias"] = ((o,), 0)
    return spec


def logits(params: dict, images, c: dict, precision: str = "f32"):
    x = images.astype(jnp.float32)
    conv = 0
    for item in c["channels"]:
        if item == "M":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        w = params[f"conv{conv}"]
        x = jax.lax.conv_general_dilated(
            operand(x, precision), operand(w["kernel"], precision),
            (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST)
        x = jax.nn.relu(store(x + w["bias"], precision))
        conv += 1
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2"):
        x = jax.nn.relu(store(matmul(x, params[name]["kernel"], precision)
                              + params[name]["bias"], precision))
    return matmul(x, params["head"]["kernel"], precision) + params["head"]["bias"]


def loss_rows(params: dict, batch, c: dict, precision: str = "f32"):
    """Summed cross-entropy over the rows of `batch` = (images, labels)."""
    images, labels = batch
    lg = logits(params, images, c, precision)
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])


def units(batch) -> int:
    return batch[0].shape[0]
