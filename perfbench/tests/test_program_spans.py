"""The readers of the program's own spans, on synthetic traces (a device
plane beside "tpunet:" host spans) and in the two adapters at a tiny size on
the CPU, where no device plane exists."""

import math

import pytest

from perfbench import harness, trace
from perfbench.adapters import _models, dp_ranks, train_step
from perfbench.readers import dcn_bridge_parts, program_spans, span_self_time, span_sum

SEND = "%io_callback.6 = (f32[8]{0}, u32[], token[]) send(f32[8]{0} %x, token[] %t), channel_id=2, is_host_transfer=true"
SEND_DONE = "%io_callback.7 = token[] send-done((f32[8]{0}, u32[], token[]) %io_callback.6), channel_id=2, is_host_transfer=true"
RECV_DONE = "%io_callback.9 = (f32[8]{0}, token[]) recv-done((f32[8]{0}, u32[], token[]) %io_callback.8), channel_id=3, is_host_transfer=true"


def two_steps(with_send: bool = True):
    """Two steps of 10 s: compute 1 s, and (an in-jit collective's) send at
    +1.0, send-done +1.0..1.2, recv-done +1.2..9.0; the bridge runs
    +3.0..7.0 with stage_in +3.0..3.5, the collective +3.5..6.5 and
    stage_out +6.5..7.0. So host 1.0 a step, half of it each stage."""
    dev, prog = trace.Trace(), trace.Trace()
    ops = dev.ops.setdefault("/device:TPU:0", [])
    for k in range(2):
        t = 10.0 * k
        ops.append(("%fusion.1 = f32[8]{0} fusion(...)", t, 1.0))
        if with_send:
            ops.append((SEND, t + 1.0, 0.001))
        ops.append((SEND_DONE, t + 1.0, 0.2))
        ops.append((RECV_DONE, t + 1.2, 7.8))
        prog.host += [("train.step", t, 9.5), ("train.step_fn", t + 0.1, 9.0),
                      ("train.loss_fetch", t + 9.2, 0.1),
                      ("dcn.bridge", t + 3.0, 4.0),
                      ("dcn.bridge.stage_in", t + 3.0, 0.5),
                      ("dcn.bridge.collective", t + 3.5, 3.0),
                      ("dcn.bridge.stage_out", t + 6.5, 0.5)]
    return dev, prog


HOST = harness.load("metrics", "dcn_bridge_host_s_per_step")["params"]
STAGES = {name: harness.load("metrics", f"dcn_bridge_{name}_s_per_step")["params"]
          for name in ("stage_in", "stage_out")}


def ctx_of(dev, prog, steps=2):
    return {"trace": dev, "program_trace": prog, "lo": 0.0, "hi": 20.0,
            "run": {"traced_steps": steps}}


def test_bridge_host_part():
    assert dcn_bridge_parts.read(ctx_of(*two_steps()), HOST) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["stage_in", "stage_out"])
def test_bridge_stages(name):
    assert span_sum.read(ctx_of(*two_steps()), STAGES[name]) == pytest.approx(0.5)


def test_bridge_host_needs_no_device_plane():
    """The host transfers it was once cut beside are gone from the device's
    timeline (PR 25): the spans alone give it, with or without them."""
    dev, prog = two_steps(with_send=False)
    assert dcn_bridge_parts.read(ctx_of(dev, prog), HOST) == pytest.approx(1.0)
    assert dcn_bridge_parts.read(ctx_of(trace.Trace(), prog), HOST) == pytest.approx(1.0)


def test_bridge_parts_say_nothing_where_there_is_nothing():
    dev, prog = two_steps()
    for read, params in [(dcn_bridge_parts.read, HOST)] + [
            (span_sum.read, p) for p in STAGES.values()]:
        assert read(ctx_of(dev, trace.Trace()), params) is None
        assert read(ctx_of(dev, None), params) is None
    assert dcn_bridge_parts.read(ctx_of(dev, prog, steps=0), HOST) is None
    # no trace directory at all
    assert program_spans.load({"run": {"native_dir": "/nonexistent/native"}}) is None
    assert program_spans.load({"run": {}}) is None


def test_a_span_cut_by_the_window_is_left_out():
    dev, prog = two_steps()
    c = dict(ctx_of(dev, prog), hi=16.0)  # the second bridge ends at 17
    assert dcn_bridge_parts.read(c, HOST) == pytest.approx(0.5)  # 1.0 over 2 steps
    assert span_sum.read(c, STAGES["stage_in"]) == pytest.approx(0.5)  # the mean of the one whole


def test_self_time():
    dev, prog = two_steps()
    params = {"span": "train.step", "less": ["train.step_fn", "train.loss_fetch"]}
    assert span_self_time.read(ctx_of(dev, prog), params) == pytest.approx(0.4)
    assert span_self_time.read(ctx_of(dev, trace.Trace()), params) is None
    assert span_self_time.read(ctx_of(dev, None), params) is None


def test_train_step_traced_run_reports_fits_own_time(train_cell):
    res = train_step.run(train_cell, 5, 2.0, True, platform="cpu")
    assert res["correct"]
    assert 0 < res["metrics"]["fit_host_s_per_step"]["value"] < 0.1


@pytest.mark.parametrize("ffi", ["0", None], ids=["ffi_off", "ffi_as_set"])
def test_dp_ranks_flat_step_counts_one_bridge_call_a_step(dp_cell, ffi):
    """Since PR 25 the flat cross-host step exchanges at a program boundary
    whatever TPUNET_FFI_COLLECTIVES says: one bridge call of the whole
    gradient a step, in one chunk at this size, with its stages' spans."""
    if ffi is not None:
        dp_cell["env"] = dict(dp_cell.get("env", {}), TPUNET_FFI_COLLECTIVES=ffi)
    res = dp_ranks.run(dp_cell, 12345, 2.0, True, platform="cpu")
    assert res["correct"], res["compared"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    spec = _models.reference(dp_cell["config"]).param_spec(dp_cell["config"])
    assert m["dcn_bridge_bytes_per_call"] == 4 * sum(
        math.prod(shape) for shape, _ in spec.values())
    assert m["dcn_bridge_chunks_per_call"] == 1.0
    assert m["dcn_bridge_stage_in_s_per_step"] > 0 and m["dcn_bridge_stage_out_s_per_step"] > 0
    assert (m["dcn_bridge_stage_in_s_per_step"] + m["dcn_bridge_stage_out_s_per_step"]
            <= m["dcn_bridge_host_s_per_step"])
    assert not {"dcn_bridge_s_per_step", "dcn_bridge_d2h_s_per_step",
                "dcn_bridge_h2d_s_per_step"} & set(m)
