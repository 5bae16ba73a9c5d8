"""The Nemotron-H cell's adapter at a toy size on the CPU, sound and with the
faults the timed path can have (the adapter's own three, and two planted in
the program: the scan's state dropped between chunks, the MTP loss left
out); the control; the readers of the scan's and the latent experts'
kernels on hand-made traces; the counters and the published counts.

`planted(fault)` is also what a chip run of the cell's faults uses:

    with planted("state_dropped"):
        res = train_step.run(harness.cell("nemotron-train-s8192"), seed, 10.0, False)
"""

import contextlib
import copy

import pytest

from perfbench import compare, harness
from perfbench.adapters import _train, train_step
from perfbench.harness import check_line
from perfbench.models import nemotron_h
from perfbench.tests.test_readers import PEAKS, traced

CELL = "nemotron-train-s8192"
PLANTED = ("state_dropped", "mtp_dropped")


@contextlib.contextmanager
def planted(fault: str):
    """The program with a fault in its timed path. "state_dropped": every
    chunk of the scan starts from a zero state (the sequence is handed to
    the kernels as rows of one chunk each). "mtp_dropped": the train step's
    loss leaves the MTP module's out (its weight 0)."""
    import tpunet.ops.ssd_scan as ops

    scan, build = ops.ssd_scan, nemotron_h.build

    def chunks_alone(x, dt, a, b, c, chunk, interpret=None):
        rows, seq = x.shape[:2]
        cut = lambda t: t.reshape(rows * seq // chunk, chunk, *t.shape[2:])  # noqa: E731
        return scan(cut(x), cut(dt), a, cut(b), cut(c), chunk, interpret).reshape(x.shape)

    if fault == "state_dropped":
        ops.ssd_scan = chunks_alone
    elif fault == "mtp_dropped":
        nemotron_h.build = lambda cfg, cell: build(cfg, cell).clone(mtp_loss_weight=0.0)
    else:
        raise ValueError(f"no planted fault {fault!r}")
    try:
        yield
    finally:
        ops.ssd_scan, nemotron_h.build = scan, build


@pytest.fixture
def nemotron_cell():
    c = copy.deepcopy(harness.cell(CELL))
    c["config"].update(hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
                       ssm_state_size=16, chunk_size=16, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=8, n_routed_experts=16,
                       n_routed_experts_held=4, num_experts_per_tok=5,
                       moe_latent_size=16, moe_intermediate_size=24,
                       moe_shared_expert_columns_held=12, vocab_size=64,
                       num_hidden_layers=5, hybrid_override_pattern="MEM*E",
                       compute_dtype="float32", initializer_range=0.2)
    c["traffic"].update(batch=2, seq=128, pool=4)
    # delta_norm_gap: AdamW moves every element by about its learning rate
    # whatever the gradient's size, so the A_log of a head whose state
    # vanishes at once (a gradient near 0, known to a few digits) reads
    # up to 8e-4 from float32 round-off over three steps
    c.update(kernels=None, trace_seconds=1, reference_rows=1,
             limits={"loss_gap": 1e-5, "grad_norm_gap": 1e-4, "delta_norm_gap": 2e-3,
                     "grad_diff": 1e-4})
    return c


def test_nemotron_sound_run(nemotron_cell):
    res = train_step.run(nemotron_cell, 2 ** 31 + 5, 1.0, False, platform="cpu")
    check_line(res, traced=False)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"step_s", "setup_s"}


def test_nemotron_traced_run_says_nothing_of_a_device_it_has_not(nemotron_cell):
    res = train_step.run(nemotron_cell, 6, 2.0, True, platform="cpu")
    check_line(res, traced=True)
    assert res["correct"], res["compared"]
    assert not {"ssd_fwd_roofline", "ssd_bwd_roofline", "ssd_kernel_share.step",
                "latent_moe_gmm_fwd_roofline", "latent_moe_gmm_bwd_roofline",
                "train_step_mfu"} & set(res["metrics"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_nemotron_faults_of_the_adapter_come_out_incorrect(nemotron_cell, fault):
    res = train_step.run(nemotron_cell, 7, 0.5, False, platform="cpu", fault=fault)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("fault", PLANTED)
def test_nemotron_faults_planted_in_the_program_come_out_incorrect(nemotron_cell, fault):
    with planted(fault):
        res = train_step.run(nemotron_cell, 7, 0.5, False, platform="cpu")
    assert not res["correct"], res["compared"]


def test_nemotron_control_fails_the_comparison(nemotron_cell):
    """float32 toy: the control is the reference in bfloat16."""
    exact = _train.reference_steps(nemotron_cell, 11, "f32")
    control = _train.reference_steps(nemotron_cell, 11, "bf16")
    assert not compare.judge(compare.train(control, exact)[0], nemotron_cell["limits"])[0]
    again = _train.reference_steps(nemotron_cell, 11, "f32")
    assert compare.judge(compare.train(again, exact)[0], nemotron_cell["limits"])[0]


def test_the_cells_files_are_read_by_the_harness():
    import importlib

    cell = harness.cell(CELL)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        "nemotron3-super-120b-a12b-tp8-l11", "tokens-b2-s8192", 1)
    assert cell["config"]["family"] == cell["config"]["reference"] == "nemotron_h"
    assert harness.metric_names(CELL, traced=False) == ["step_s", "setup_s"]
    traced_names = harness.metric_names(CELL, traced=True)
    assert set(traced_names) == {
        "train_step_mfu", "device_idle_share.step", "ssd_fwd_roofline", "ssd_bwd_roofline",
        "ssd_kernel_share.step", "latent_moe_gmm_fwd_roofline", "latent_moe_gmm_bwd_roofline",
        "fit_host_s_per_step", "moe_kernel_share.step"}
    for name in traced_names:
        importlib.import_module(f"perfbench.readers.{harness.load('metrics', name)['reader']}")
    assert set(cell["limits"]) == {"loss_gap", "loss_gap_first", "grad_norm_gap",
                                   "delta_norm_gap", "grad_diff"}


def test_published_counts_and_the_cut():
    cfg = harness.load("configs", "nemotron3-super-120b-a12b-tp8-l11")
    # a Mamba mixer 13.7 M, attention 5.2 M, a latent expert layer 60.0 M
    assert nemotron_h.block_params(cfg, "M") == (4096 * 2320 + 1024 * 4096 + 4 * 1280
                                                  + 1280 + 3 * 16 + 1024 + 4096)
    assert nemotron_h.block_params(cfg, "*") == 2 * 4096 * 128 * 5 + 4096
    assert nemotron_h.block_params(cfg, "E") == (4096 * (512 + 2 * 1024 + 2 * 672) + 512
                                                  + 8 * 2 * 1024 * 2688 + 4096)
    assert nemotron_h.params(cfg) == 607_038_960
    whole = dict(cfg, num_hidden_layers=88, vocab_size=131_072, mamba_num_heads=128,
                 n_groups=8, num_attention_heads=32, num_key_value_heads=2,
                 n_routed_experts_held=512, moe_shared_expert_columns_held=5376,
                 hybrid_override_pattern=cfg["published"]["hybrid_override_pattern"])
    assert 120e9 < nemotron_h.params(whole) < 125e9  # "120B", the MTP module included
    assert nemotron_h.expected_rows(cfg, 16384) == 5632
    assert 35.8e12 < nemotron_h.train_flops(cfg, {"batch": 2, "seq": 8192}) < 36.0e12


def test_scan_counters():
    cfg = {"chunk_size": 4, "mamba_num_heads": 2, "mamba_head_dim": 3,
           "ssm_state_size": 5, "n_groups": 1}
    got = nemotron_h.layer_ssd(cfg, 1, 8)
    chunks = 1 * 2 * 2
    assert got["fwd"][0] == chunks * ((5 + 3) * 4 * 5 + 4 * 4 * 5 * 3)
    assert got["bwd"][0] == chunks * ((3 * 5 + 2 * 3) * 4 * 5 + 10 * 4 * 5 * 3)
    u, bc, cs = 8 * 2 * 3 * 2, 2 * 8 * 5 * 2, 8 * 2 * 4
    assert got["fwd"][1] == 2 * u + bc + cs
    assert got["bwd"][1] == 3 * u + 2 * bc + 2 * cs
    assert nemotron_h.layer_ssd(cfg, 1, 7)["fwd"] == (got["fwd"][0], got["fwd"][1] * 7 / 8)


# one pass of a block, by metric: the HLO lines of its kernels as the chip names them
CALL = ' custom-call(bf16[32,8192,64]{2,1,0} %x), custom_call_target="tpu_custom_call"'
PASS = {"ssd_fwd_roofline": ["%checkpoint_ssd_fwd.3 = bf16[32,8192,64]{2,1,0}" + CALL],
        "ssd_bwd_roofline": ["%transpose_ssd_bwd.4 = bf16[32,8192,64]{2,1,0}" + CALL],
        "latent_moe_gmm_fwd_roofline": ["%moe_gmm_fwd.7 = bf16[32768,2688]{1,0}" + CALL] * 2,
        "latent_moe_gmm_bwd_roofline": ["%jvp_moe_gmm_dx.8 = bf16[32768,1024]{1,0}" + CALL] * 2
                                       + ["%moe_tgmm_dw.9 = f32[8,1024,2688]{2,1,0}" + CALL] * 2}
KIND = {"ssd_fwd_roofline": "M", "ssd_bwd_roofline": "M",
        "latent_moe_gmm_fwd_roofline": "E", "latent_moe_gmm_bwd_roofline": "E"}


def _read(metric: str, calls: int, drop: float = 0):
    from perfbench.readers import layer_kind_roofline

    spec = harness.load("metrics", metric)
    cell = harness.cell(CELL)
    blocks = nemotron_h.layers_of(cell["config"], KIND[metric])
    t, hi = traced(PASS[metric], blocks, calls, 3, drop=drop)
    run = {"traced_steps": 3}
    ctx = {"trace": t, "lo": 0.0, "hi": hi, "run": run, "cell": cell, "peaks": PEAKS}
    return layer_kind_roofline.read(ctx, spec["params"]), run


@pytest.mark.parametrize("metric", sorted(PASS))
def test_the_readers_count_calls_over_the_blocks_of_their_kind(metric):
    once, run1 = _read(metric, 1)
    twice, run2 = _read(metric, 2)
    key = next(iter(run1["kernel_calls"]))
    assert run1["kernel_calls"] == {key: 1} and run2["kernel_calls"] == {key: 2}
    assert 0 < once < 100 and twice == pytest.approx(once, rel=1e-12)
    cut, run = _read(metric, 2, drop=0.5)
    assert cut is None and "no whole number" in run["roofline_skipped"][key]


def test_the_scan_share_reads_every_ssd_kernel():
    from perfbench.readers import layer_kind_roofline

    t, hi = traced(PASS["ssd_fwd_roofline"] + PASS["ssd_bwd_roofline"], 5, 1, 3)
    ctx = {"trace": t, "lo": 0.0, "hi": hi, "run": {"traced_steps": 3},
           "cell": harness.cell(CELL), "peaks": PEAKS}
    got = layer_kind_roofline.read(ctx, harness.load("metrics", "ssd_kernel_share.step")["params"])
    assert got == pytest.approx(100 * 0.04 / 0.042)
