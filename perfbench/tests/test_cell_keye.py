"""The Keye cell's adapter at a toy size on the CPU, sound and with the
faults the timed path can have; the control; the counters of the selecting
attention's operations and bytes and the published counts."""

import copy

import pytest

from perfbench import compare, harness
from perfbench.adapters import _train, train_step
from perfbench.harness import check_line
from perfbench.models import keye


@pytest.fixture
def keye_cell():
    c = copy.deepcopy(harness.cell("keye-train-s8192"))
    c["config"].update(hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, moe_intermediate_size=24, num_experts=8,
                       num_experts_per_tok=3, num_local_experts=2, experts_first=2,
                       vocab_size=64, num_hidden_layers=2, initializer_range=0.3,
                       embed_initializer_range=0.3, compute_dtype="float32",
                       sa_config=dict(c["config"]["sa_config"], indexer_num_heads=3,
                                      indexer_head_dim=8, topk=24))
    c["traffic"].update(batch=2, seq=128, pool=4)
    c.update(kernels=None, trace_seconds=1, reference_rows=1,
             limits={"loss_gap": 1e-5, "grad_norm_gap": 1e-4, "delta_norm_gap": 2e-4})
    return c


def test_keye_sound_run(keye_cell):
    res = train_step.run(keye_cell, 2 ** 31 + 5, 1.0, False, platform="cpu")
    check_line(res, traced=False)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"step_s", "setup_s"}


def test_keye_traced_run_says_nothing_of_a_device_it_has_not(keye_cell):
    res = train_step.run(keye_cell, 6, 2.0, True, platform="cpu")
    check_line(res, traced=True)
    assert res["correct"], res["compared"]
    assert not {"dsa_index_roofline", "dsa_attn_fwd_roofline", "dsa_attn_bwd_roofline",
                "dsa_kernel_share.step", "moe_gmm_fwd_roofline", "moe_gmm_bwd_roofline",
                "moe_kernel_share.step", "train_step_mfu"} & set(res["metrics"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_keye_faults_come_out_incorrect(keye_cell, fault):
    res = train_step.run(keye_cell, 7, 0.5, False, platform="cpu", fault=fault)
    assert not res["correct"], res["compared"]


def test_keye_control_fails_the_comparison(keye_cell):
    """float32 toy: the control is the reference in bfloat16."""
    exact = _train.reference_steps(keye_cell, 11, "f32")
    control = _train.reference_steps(keye_cell, 11, "bf16")
    assert not compare.judge(compare.train(control, exact)[0], keye_cell["limits"])[0]
    again = _train.reference_steps(keye_cell, 11, "f32")
    assert compare.judge(compare.train(again, exact)[0], keye_cell["limits"])[0]


def test_published_counts_and_the_cut():
    cfg = harness.load("configs", "keye-vl2-30b-a3b-ep8-l4")
    assert keye.held(cfg) == (0, 16)
    # attention 18.87 M, indexer 2.26 M, router 0.26 M, 16 experts of 4.72 M
    assert keye.layer_params(cfg) == (18_874_368 + 2_260_992 + 262_144
                                      + 16 * 4_718_592 + 2 * 2048 + 2 * 128 + 2 * 64)
    assert keye.params(cfg) == 465_391_104
    whole = dict(cfg, num_hidden_layers=48, num_local_experts=128, vocab_size=151_936)
    assert 30.0e9 < keye.params(whole) < 31.0e9  # "30B"
    assert keye.expected_rows(cfg, 16384) == 16_384
    assert keye.selected_pairs(8192, 2048) == 14_681_088
    assert keye.causal_pairs(8192) == 33_558_528
    assert 23.3e12 < keye.train_flops(cfg, {"batch": 2, "seq": 8192}) < 23.5e12


def test_selecting_attention_counters():
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
           "sa_config": {"indexer_num_heads": 3, "indexer_head_dim": 8, "topk": 6}}
    got = keye.layer_dsa(cfg, 2, 32)
    pairs = 2 * sum(min(t + 1, 6) for t in range(32))
    assert got["index"][0] == 2 * 3 * 8 * 2 * (32 * 33 // 2)
    assert got["index"][1] == 2 * (32 * (24 + 8) * 2 + 32 * 3 * 4 + (32 * 33 // 2) * 4)
    assert got["attn_fwd"][0] == 4 * 64 * pairs
    assert got["attn_bwd"][0] == 2.5 * got["attn_fwd"][0]
    assert got["attn_fwd"][1] == 2 * 32 * 16 * (2 * 4 + 2 * 2) * 2
    assert keye.selected_pairs(4, 6) == 10  # a row shorter than top_k keeps every causal pair
