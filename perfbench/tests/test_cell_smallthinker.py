"""The SmallThinker cell's adapter at a toy size on the CPU, sound and with
the faults the timed path can have; the control; the counters of the
grouped products' operations and bytes and the published counts."""

import copy

import pytest

from perfbench import compare, harness
from perfbench.adapters import _train, train_step
from perfbench.harness import check_line
from perfbench.models import smallthinker


@pytest.fixture
def st_cell():
    c = copy.deepcopy(harness.cell("smallthinker-train-s8192"))
    c["config"].update(hidden_size=48, num_attention_heads=7, num_key_value_heads=1,
                       head_dim=16, moe_ffn_hidden_size=24, moe_num_primary_experts=8,
                       moe_num_active_primary_experts=3, moe_num_primary_experts_held=2,
                       moe_experts_first=2, vocab_size=64, sliding_window_size=64,
                       num_hidden_layers=4, initializer_range=0.3,
                       compute_dtype="float32")
    c["traffic"].update(batch=2, seq=128, pool=4)
    c.update(kernels=None, trace_seconds=1, reference_rows=1,
             limits={"loss_gap": 1e-5, "grad_norm_gap": 1e-4, "delta_norm_gap": 2e-4})
    return c


def test_smallthinker_sound_run(st_cell):
    res = train_step.run(st_cell, 2 ** 31 + 5, 1.0, False, platform="cpu")
    check_line(res, traced=False)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"step_s", "setup_s"}


def test_smallthinker_traced_run_says_nothing_of_a_device_it_has_not(st_cell):
    res = train_step.run(st_cell, 6, 2.0, True, platform="cpu")
    check_line(res, traced=True)
    assert res["correct"], res["compared"]
    assert not {"moe_gmm_fwd_roofline", "moe_gmm_bwd_roofline", "moe_kernel_share.step",
                "flash_fwd_roofline", "flash_bwd_roofline",
                "train_step_mfu"} & set(res["metrics"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_smallthinker_faults_come_out_incorrect(st_cell, fault):
    res = train_step.run(st_cell, 7, 0.5, False, platform="cpu", fault=fault)
    assert not res["correct"], res["compared"]


def test_smallthinker_control_fails_the_comparison(st_cell):
    """float32 toy: the control is the reference in bfloat16."""
    exact = _train.reference_steps(st_cell, 11, "f32")
    control = _train.reference_steps(st_cell, 11, "bf16")
    assert not compare.judge(compare.train(control, exact)[0], st_cell["limits"])[0]
    again = _train.reference_steps(st_cell, 11, "f32")
    assert compare.judge(compare.train(again, exact)[0], st_cell["limits"])[0]


def test_published_counts_and_the_cut():
    cfg = harness.load("configs", "smallthinker-21b-a3b-ep4-l4")
    assert smallthinker.held(cfg) == (0, 16)
    assert smallthinker.pattern(cfg) == ((False, False),) + ((True, True),) * 3
    assert smallthinker.layer_windows(cfg) == [None, 4096, 4096, 4096]
    assert smallthinker.layer_params(cfg) == 20_971_520 + 163_840 + 5_120 + 16 * 5_898_240
    assert smallthinker.params(cfg) == 656_529_920
    whole = dict(cfg, num_hidden_layers=52, moe_num_primary_experts_held=64,
                 vocab_size=151_936)
    assert 21.4e9 < smallthinker.params(whole) < 21.6e9
    mix = {"batch": 2, "seq": 8192}
    assert smallthinker.expected_rows(cfg, 16384) == 24_576
    assert 30.6e12 < smallthinker.train_flops(cfg, mix) < 30.8e12


def test_grouped_counters():
    assert smallthinker.gmm_flops(100, 8, 4) == 2 * 100 * 8 * 4
    assert smallthinker.gmm_bytes(100, 3, 8, 4) == 100 * 8 * 2 + 3 * 8 * 4 * 2 + 100 * 4 * 2
    assert smallthinker.tgmm_bytes(100, 3, 8, 4) == 100 * 12 * 2 + 3 * 8 * 4 * 4
    cfg = {"hidden_size": 8, "moe_ffn_hidden_size": 4, "moe_num_primary_experts": 4,
           "moe_num_active_primary_experts": 2, "moe_num_primary_experts_held": 2}
    got = smallthinker.layer_gmm(cfg, 100)  # 100 rows of the 200 pairs fall here
    assert got["fwd"][0] == 3 * 2 * 100 * 8 * 4 and got["bwd"][0] == 2 * got["fwd"][0]
    fwd_bytes = 2 * smallthinker.gmm_bytes(100, 2, 8, 4) + smallthinker.gmm_bytes(100, 2, 4, 8)
    assert got["fwd"][1] == fwd_bytes
    assert got["bwd"][1] == fwd_bytes + 2 * smallthinker.tgmm_bytes(100, 2, 8, 4) \
        + smallthinker.tgmm_bytes(100, 2, 4, 8)
