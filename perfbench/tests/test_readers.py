"""The kernel roofline readers and the span sums on hand-made traces: how
often a kernel ran is counted in the trace, a share does not depend on it,
a layer's work is counted by its kind, `kernels` in a cell's file is a
minimum, and the bridge's stages sum inside their exchange."""

import json

import pytest

from perfbench import flops, harness, trace
from perfbench.readers import (eva_roofline, flash_roofline, kernel_roofline,
                               moe_roofline, span_sum)

PEAKS = harness.peaks("TPU v5 lite")
TPU = "/device:TPU:0"
CALL = ' custom-call(bf16[2,8192,32,128]{3,2,1,0} %x), custom_call_target="tpu_custom_call"'
# one pass of a layer, by metric: the HLO lines of its kernels as the chip names them
PASS = {
    "flash_fwd_roofline": ["%attn.1 = (bf16[2,8192,32,128]{3,2,1,0}, f32[2,32,8192]{2,1,0})" + CALL],
    "flash_bwd_roofline": ["%attn.2 = bf16[2,8192,32,128]{3,2,1,0}" + CALL,
                           "%attn.3 = (bf16[2,8192,8,128]{3,2,1,0}, bf16[2,8192,8,128]{3,2,1,0})" + CALL],
    "eva_fwd_roofline": [f"%checkpoint_eva_{k}_fwd.4 = (bf16[1,16384,32,128]{{3,2,1,0}}, f32[1])" + CALL
                         for k in ("local", "remote")],
    "eva_bwd_roofline": [f"%transpose_jvp_eva_{k}_{g}.9 = bf16[1,16384,32,128]{{3,2,1,0}}" + CALL
                         for k in ("local", "remote") for g in ("dq", "dkv")],
    "moe_gmm_fwd_roofline": ["%moe_gmm_fwd.7 = bf16[105984,768]{1,0}" + CALL] * 3,
    "moe_gmm_bwd_roofline": ["%jvp_moe_gmm_dx.8 = bf16[105984,2560]{1,0}" + CALL] * 3
                            + ["%moe_tgmm_dw.9 = f32[16,2560,768]{2,1,0}" + CALL] * 3,
}
CELLS = {"flash_fwd_roofline": ["mistral7b-train-s8192", "smallthinker-train-s8192"],
         "flash_bwd_roofline": ["mistral7b-train-s8192", "smallthinker-train-s8192"],
         "eva_fwd_roofline": ["evabyte-train-s16384"], "eva_bwd_roofline": ["evabyte-train-s16384"],
         "moe_gmm_fwd_roofline": ["smallthinker-train-s8192"],
         "moe_gmm_bwd_roofline": ["smallthinker-train-s8192"]}
READERS = {"flash_roofline": flash_roofline, "eva_roofline": eva_roofline,
           "moe_roofline": moe_roofline}


EACH = 0.02  # seconds a kernel event, slower than any of these kernels' least time


def traced(names, layers: int, calls: int, steps: int, each: float = EACH, drop: float = 0):
    """A device plane on which every name ran `calls` times a layer a step,
    `each` seconds a time, between XLA's own fusions; the last `drop`
    of a step is cut off, as by a window that closed inside a step."""
    ops, at = [], 0.0
    for _ in range(steps * layers * calls):
        for name in names:
            ops.append(("%fusion.1 = bf16[2,8192,4096]{2,1,0} fusion(...)", at, 0.001))
            ops.append((name, at + 0.001, each))
            at += 0.001 + each
    ops = ops[:len(ops) - 2 * int(drop * layers * calls * len(names))]
    return trace.Trace(ops={TPU: ops}, host=[("window", 0.0, at)]), at


def read(metric: str, cell_name: str, calls: int, steps: int = 3, drop: float = 0):
    spec = harness.load("metrics", metric)
    cell = harness.cell(cell_name)
    t, hi = traced(PASS[metric], cell["config"]["num_hidden_layers"], calls, steps, drop=drop)
    run = {"traced_steps": steps}
    ctx = {"trace": t, "lo": 0.0, "hi": hi, "run": run, "cell": cell, "peaks": PEAKS}
    return READERS[spec["reader"]].read(ctx, spec["params"]), run


def test_count_by_name_beside_seconds_by_name():
    ev = [("a.1", 0.0, 1.0), ("b", 1.0, 1.0), ("a.2", 2.0, 0.5)]
    assert trace.count_by_name(ev, r"^a\.") == 2
    assert trace.seconds_by_name(ev, r"^a\.") == pytest.approx(1.5)
    assert trace.count_by_name(ev, "^c") == 0


@pytest.mark.parametrize("events, want", [
    (2 * 10 * 4 * 3, (2.0, 2)),      # twice a layer a step: forward and remat's recompute
    (1 * 10 * 4 * 3, (1.0, 1)),      # once: a program that keeps the kernel's outputs
    (2 * 10 * 4 * 3 - 3, (1.975, None)),  # a step cut by the window
    (0, (0.0, None))])
def test_calls_found(events, want):
    found, calls = kernel_roofline.calls_found(events, devices=1, steps=10, layers=4,
                                               kernels_per_call=3)
    assert (pytest.approx(found), calls) == want


@pytest.mark.parametrize("metric, cell", [(m, c) for m in PASS for c in CELLS[m]])
def test_a_share_does_not_depend_on_how_often_the_kernel_ran(metric, cell):
    once, run1 = read(metric, cell, calls=1)
    twice, run2 = read(metric, cell, calls=2)
    key = next(iter(run1["kernel_calls"]))
    assert run1["kernel_calls"] == {key: 1} and run2["kernel_calls"] == {key: 2}
    assert 0 < once < 100 and twice == pytest.approx(once, rel=1e-12)
    assert run1["roofline_bound"] == run2["roofline_bound"] == {key: "flops"}


@pytest.mark.parametrize("metric", list(PASS))
def test_a_cut_step_gives_no_share_and_says_why(metric):
    got, run = read(metric, CELLS[metric][0], calls=2, drop=0.5)
    assert got is None and "kernel_calls" not in run
    assert "no whole number" in next(iter(run["roofline_skipped"].values()))


def test_flash_reader_against_hand_worked_flops():
    """Each kernel event takes EACH seconds. Mistral: every layer under the one 4096
    window, 3072.25 keys a query at s8192. SmallThinker: 28 heads of 128,
    one full causal layer (4096.5 keys) and three window layers."""
    b, s = 2, 8192
    mistral = 4 * 4096 * 3072.25 * b * s                   # one layer forward
    assert read("flash_fwd_roofline", "mistral7b-train-s8192", 2)[0] == pytest.approx(
        100 * mistral / 197e12 / EACH)
    assert read("flash_bwd_roofline", "mistral7b-train-s8192", 1)[0] == pytest.approx(
        100 * 2.5 * mistral / 197e12 / (2 * EACH))       # dq and dkv: two kernels a pass
    period = 4 * 3584 * (4096.5 + 3 * 3072.25) * b * s      # the four layers forward
    assert read("flash_fwd_roofline", "smallthinker-train-s8192", 2)[0] == pytest.approx(
        100 * period / 197e12 / (4 * EACH))
    assert read("flash_bwd_roofline", "smallthinker-train-s8192", 1)[0] == pytest.approx(
        100 * 2.5 * period / 197e12 / (4 * 2 * EACH))
    cfg = harness.load("configs", "smallthinker-21b-a3b-ep4-l4")
    assert flash_roofline.attention_fwd(cfg, b, s) == pytest.approx(period)
    one_window = dict(cfg, family="mistral", sliding_window=4096)  # a family without kinds
    assert flash_roofline.attention_fwd(one_window, b, s) == pytest.approx(
        4 * flops.attention_flops_fwd(one_window, b, s))


def test_readers_say_nothing_without_the_kernels_or_the_peaks():
    spec = harness.load("metrics", "flash_fwd_roofline")
    cell = harness.cell("mistral7b-train-s8192")
    t, hi = traced(PASS["eva_fwd_roofline"], 2, 1, 3)      # another model's kernels
    ctx = {"trace": t, "lo": 0.0, "hi": hi, "run": {"traced_steps": 3}, "cell": cell,
           "peaks": PEAKS}
    assert flash_roofline.read(ctx, spec["params"]) is None
    assert flash_roofline.read(dict(ctx, peaks=None), spec["params"]) is None
    assert flash_roofline.read(dict(ctx, run={}), spec["params"]) is None
    share = harness.load("metrics", "eva_kernel_share.step")["params"]
    assert eva_roofline.read(ctx, share) == pytest.approx(100 * EACH / (EACH + 0.001))
    assert moe_roofline.read(ctx, harness.load("metrics", "moe_kernel_share.step")["params"]) is None


class Compiled:
    def __init__(self, kernels: int):
        self.text = "\n".join(["%fusion = f32[8] fusion()"] + [harness.KERNEL] * kernels)

    def as_text(self):
        return self.text


@pytest.mark.parametrize("held, least, refused", [
    (8, 6, False), (6, 6, False),      # remat's recompute on top of the least, or not
    (5, 6, True), (0, 6, True),        # a kernel fell back to einsums; all of them did
    (0, 0, False), (3, None, False)])  # a cell with no kernel; a twin, the CPU
def test_kernels_in_a_cells_file_is_a_minimum(held, least, refused):
    if refused:
        with pytest.raises(SystemExit, match=f"holds {held} .* at least {least}"):
            harness.count_kernels(Compiled(held), least)
    else:
        assert harness.count_kernels(Compiled(held), least) == held


def test_the_cells_state_the_least_kernels_of_a_sound_program():
    """A layer: flash forward, dq, dkv; EVA's two forward and four backward;
    three products forward, three against the transposed matrices and
    three matrices' gradients. Every forward once."""
    least = {"mistral7b-train-s8192": 2 * 3, "evabyte-train-s16384": 4 * 6,
             "smallthinker-train-s8192": 4 * (3 + 9), "vgg16-dp2-tcp": 0, "vgg16-dp4-shm": 0}
    assert {w["name"]: harness.cell(w["name"])["kernels"]
            for w in harness.manifest()["workloads"]} == least


def bridge_of_three_chunks():
    """Two exchanges of three chunks: stage_in 0.10, 0.001, 0.002; a ring of
    0.2 a chunk; stage_out 0.003 a chunk. The second exchange's last
    stage_out lies past the window's end, so the exchange is not whole."""
    prog = trace.Trace()
    for k in range(2):
        t0 = 10.0 * k
        at = t0 + 1.0
        for wait in (0.10, 0.001, 0.002):
            prog.host += [("dcn.bridge.stage_in", at, wait),
                          ("dcn.bridge.collective", at + wait, 0.2),
                          ("dcn.bridge.stage_out", at + wait + 0.2, 0.003)]
            at += wait + 0.2 + 0.003
        prog.host.append(("dcn.bridge", t0 + 1.0, at - t0 - 1.0))
    return prog


def test_span_sums_on_a_bridge_of_three_chunks():
    prog = bridge_of_three_chunks()
    ctx = {"program_trace": prog, "lo": 0.0, "hi": 20.0, "run": {"traced_steps": 2}}
    stage_in = harness.load("metrics", "dcn_bridge_stage_in_s_per_step")["params"]
    stage_out = harness.load("metrics", "dcn_bridge_stage_out_s_per_step")["params"]
    assert span_sum.read(ctx, stage_in) == pytest.approx(0.103)
    assert span_sum.read(ctx, stage_out) == pytest.approx(0.009)
    # with the collective they are the whole exchange, and the host's part is theirs
    from perfbench.readers import dcn_bridge_parts
    host = harness.load("metrics", "dcn_bridge_host_s_per_step")["params"]
    assert dcn_bridge_parts.read(ctx, host) == pytest.approx(0.103 + 0.009)
    # the second exchange cut by the window: the mean is over the first alone
    cut = dict(ctx, hi=11.5)
    assert span_sum.read(cut, stage_in) == pytest.approx(0.103)
    assert span_sum.sums(prog, 0.0, 11.5, "dcn.bridge", "dcn.bridge.stage_in") == [pytest.approx(0.103)]
    # nothing to read: no spans, no such child, no trace
    assert span_sum.read(dict(ctx, program_trace=trace.Trace()), stage_in) is None
    assert span_sum.read(dict(ctx, program_trace=None), stage_in) is None
    assert span_sum.read(ctx, dict(stage_in, inside="dcn.bridge.nothing")) is None


def test_chunks_a_call_is_a_ratio_of_deltas():
    from perfbench.readers import counter_ratio

    params = harness.load("metrics", "dcn_bridge_chunks_per_call")["params"]
    run = {"counters": {"tpunet_bridge_chunks_total": 170.0, "tpunet_bridge_calls_total": 10.0,
                        "tpunet_bridge_chunks_in_flight_max": 0.0}}
    assert counter_ratio.read({"run": run}, params) == 17.0
    assert counter_ratio.read({"run": {"counters": {}}}, params) is None
    json.dumps(params)
