"""perfbench's own tests: CPU, tiny sizes, from the repo's root

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests -q -p xdist -n 6 --dist loadfile"""

import copy
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import harness  # noqa: E402


def tiny_mistral(cfg: dict) -> None:
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, intermediate_size=128, vocab_size=256,
               sliding_window=128, num_hidden_layers=2,
               compute_dtype="float32", param_dtype="float32")


@pytest.fixture
def train_cell():
    c = copy.deepcopy(harness.cell("mistral7b-train-s8192"))
    tiny_mistral(c["config"])
    c["traffic"].update(batch=4, seq=256, pool=4)
    c.update(kernels=None, trace_seconds=1, reference_rows=2,
             limits={"loss_gap": 1e-5, "grad_norm_gap": 2e-5, "delta_norm_gap": 1e-4})
    return c


@pytest.fixture
def dp_cell():
    c = copy.deepcopy(harness.cell("vgg16-dp2-tcp"))
    c["config"].update(image_size=32, channels=[8, "M", 16, "M", 16, "M", 16, "M", 16, "M"],
                       classifier_hidden=32, num_classes=10, compute_dtype="float32")
    c["traffic"].update(batch=8, pool=4)
    c.update(reference_rows=4, trace_seconds=1,
             limits={"loss_gap": 1e-5, "grad_norm_gap": 3e-6, "delta_norm_gap": 8e-6,
                     "grad_diff": 1e-5})
    return c
