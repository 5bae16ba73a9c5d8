"""The EvaByte cell's adapter at a toy size on the CPU, sound and with each
fault the timed path can have at this size; the control; the counters of
EVA's operations and bytes against a count by enumeration."""

import copy

import pytest

from perfbench import compare, harness
from perfbench.adapters import _train, train_step
from perfbench.harness import check_line
from perfbench.models import evabyte


@pytest.fixture
def eva_cell():
    c = copy.deepcopy(harness.cell("evabyte-train-s16384"))
    c["config"].update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                       intermediate_size=128, vocab_size=64, window_size=64,
                       chunk_size=8, num_hidden_layers=2, init_std=0.08,
                       compute_dtype="float32")
    c["traffic"].update(batch=2, seq=256, pool=4)
    c.update(kernels=None, trace_seconds=1, reference_rows=1,
             limits={"loss_gap": 1e-5, "grad_norm_gap": 3e-5, "delta_norm_gap": 1e-4})
    return c


def test_evabyte_sound_run(eva_cell):
    res = train_step.run(eva_cell, 2 ** 31 + 5, 1.0, False, platform="cpu")
    check_line(res, traced=False)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"step_s", "setup_s"}


def test_evabyte_traced_run_says_nothing_of_a_device_it_has_not(eva_cell):
    res = train_step.run(eva_cell, 6, 2.0, True, platform="cpu")
    check_line(res, traced=True)
    assert res["correct"], res["compared"]
    assert not {"eva_fwd_roofline", "eva_bwd_roofline", "eva_kernel_share.step",
                "train_step_mfu"} & set(res["metrics"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_evabyte_faults_come_out_incorrect(eva_cell, fault):
    res = train_step.run(eva_cell, 7, 0.5, False, platform="cpu", fault=fault)
    assert not res["correct"], res["compared"]


def test_evabyte_control_fails_the_comparison(eva_cell):
    """float32 toy: the control is the reference in bfloat16."""
    exact = _train.reference_steps(eva_cell, 11, "f32")
    control = _train.reference_steps(eva_cell, 11, "bf16")
    assert not compare.judge(compare.train(control, exact)[0], eva_cell["limits"])[0]
    again = _train.reference_steps(eva_cell, 11, "f32")
    assert compare.judge(compare.train(again, exact)[0], eva_cell["limits"])[0]


@pytest.mark.parametrize("seq,window,chunk", [(64, 16, 4), (56, 16, 4), (16, 16, 2),
                                              (96, 32, 8)])
def test_counters_against_enumeration(seq, window, chunk):
    local = remote = 0
    for t in range(seq):
        local += sum(1 for m in range(seq) if m <= t and m // window == t // window)
        remote += sum(1 for c in range(seq // chunk)
                      if c * chunk // window < t // window)
    assert evabyte.visible_pairs(seq, window, chunk) == (local, remote)
    cfg = {"hidden_size": 8, "window_size": window, "chunk_size": chunk}
    assert evabyte.attention_flops_fwd(cfg, 3, seq) == 4 * 8 * (local + remote) * 3
    # q, k, v, o and two summaries a chunk, two bytes an element
    assert evabyte.attention_bytes_fwd(cfg, 3, seq) == 3 * 8 * 2 * (4 * seq + 2 * (seq // chunk))
    assert evabyte.attention_bytes_bwd(cfg, 3, seq) == 2 * evabyte.attention_bytes_fwd(cfg, 3, seq)


def test_published_counts():
    cfg = harness.load("configs", "evabyte-6.5b-l4")
    assert evabyte.layer_params(cfg) == 202_391_552
    assert evabyte.params(cfg) == 821_366_784
    local, remote = evabyte.visible_pairs(16384, 2048, 16)
    assert (local / 16384, remote / 16384) == (1024.5, 448.0)
    per_token = evabyte.train_flops(cfg, {"batch": 1, "seq": 16384}) / 16384
    assert 5.1e9 < per_token < 5.3e9
