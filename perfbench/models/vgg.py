"""VGG (3x3 convolutions, 2x2 pools, three dense layers): the program's
model for a configuration, and its layers, parameters and training FLOPs
from the shapes alone."""

from __future__ import annotations


def build(cfg: dict, cell: dict):
    import jax.numpy as jnp

    from tpunet.models import VGG

    return VGG(cfg=tuple(cfg["channels"]), num_classes=cfg["num_classes"],
               hidden=cfg["classifier_hidden"],
               compute_dtype=jnp.dtype(cfg["compute_dtype"]),
               classifier_dropout=cfg["classifier_dropout"])


def layers(c: dict):
    """(kind, in_ch/in_features, out, spatial) for each weighted layer."""
    size, ch = c["image_size"], c["in_channels"]
    for item in c["channels"]:
        if item == "M":
            size //= 2
        else:
            yield ("conv", ch, item, size)
            ch = item
    feat = ch * size * size
    for out in (c["classifier_hidden"], c["classifier_hidden"], c["num_classes"]):
        yield ("dense", feat, out, 1)
        feat = out


def params(c: dict) -> int:
    return sum((9 * i * o if k == "conv" else i * o) + o
               for k, i, o, _ in layers(c))


def macs_per_image(c: dict) -> int:
    return sum(9 * i * o * s * s if k == "conv" else i * o
               for k, i, o, s in layers(c))


def train_flops_per_image(c: dict) -> float:
    return 6.0 * macs_per_image(c)  # 2 FLOP a MAC; backward twice forward


def train_flops(cfg: dict, mix: dict) -> float:
    return train_flops_per_image(cfg) * mix["batch"]
