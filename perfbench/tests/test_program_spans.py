"""The readers of the program's own spans, on synthetic traces (a device
plane beside "tpunet:" host spans) and in the two adapters at a tiny size on
the CPU, where no device plane exists."""

import pytest

from perfbench import harness, trace
from perfbench.adapters import dp_ranks, train_step
from perfbench.readers import dcn_bridge_parts, program_spans, span_self_time

SEND = "%io_callback.6 = (f32[8]{0}, u32[], token[]) send(f32[8]{0} %x, token[] %t), channel_id=2, is_host_transfer=true"
SEND_DONE = "%io_callback.7 = token[] send-done((f32[8]{0}, u32[], token[]) %io_callback.6), channel_id=2, is_host_transfer=true"
RECV_DONE = "%io_callback.9 = (f32[8]{0}, token[]) recv-done((f32[8]{0}, u32[], token[]) %io_callback.8), channel_id=3, is_host_transfer=true"


def two_steps(with_send: bool = True):
    """Two steps of 10 s: compute 1 s, send at +1.0, send-done +1.0..1.2,
    recv-done +1.2..9.0; the bridge runs +3.0..7.0 with the collective at
    +3.5..6.5. So d2h 2.0, host 1.0, h2d 2.0 a step."""
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


def part(name: str) -> dict:
    """The reader's parameters as the metric's own file gives them."""
    return harness.load("metrics", f"dcn_bridge_{name}_s_per_step")["params"]


def ctx_of(dev, prog, steps=2):
    return {"trace": dev, "program_trace": prog, "lo": 0.0, "hi": 20.0,
            "run": {"traced_steps": steps}}


@pytest.mark.parametrize("name, want", [("d2h", 2.0), ("host", 1.0), ("h2d", 2.0)])
def test_bridge_parts(name, want):
    assert dcn_bridge_parts.read(ctx_of(*two_steps()), part(name)) == pytest.approx(want)


def test_bridge_d2h_without_a_send_starts_at_the_first_wait():
    dev, prog = two_steps(with_send=False)
    assert dcn_bridge_parts.read(ctx_of(dev, prog), part("d2h")) == pytest.approx(2.0)


def test_bridge_parts_say_nothing_where_there_is_nothing():
    dev, prog = two_steps()
    empty = trace.Trace()
    for name in ("d2h", "host", "h2d"):
        assert dcn_bridge_parts.read(ctx_of(dev, empty), part(name)) is None
        assert dcn_bridge_parts.read(ctx_of(dev, None), part(name)) is None
        assert dcn_bridge_parts.read(ctx_of(dev, prog, steps=0), part(name)) is None
    # spans but no device plane: the host's share alone can be told
    assert dcn_bridge_parts.read(ctx_of(empty, prog), part("d2h")) is None
    assert dcn_bridge_parts.read(ctx_of(empty, prog), part("h2d")) is None
    assert dcn_bridge_parts.read(ctx_of(empty, prog), part("host")) == pytest.approx(1.0)
    # no trace directory at all
    assert program_spans.load({"run": {"native_dir": "/nonexistent/native"}}) is None
    assert program_spans.load({"run": {}}) is None


def test_a_span_cut_by_the_window_is_left_out():
    dev, prog = two_steps()
    c = dict(ctx_of(dev, prog), hi=16.0)  # the second bridge ends at 17
    assert dcn_bridge_parts.read(c, part("host")) == pytest.approx(0.5)  # 1.0 over 2 steps


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


def test_dp_ranks_over_the_callback_bridge(dp_cell):
    dp_cell["env"] = dict(dp_cell.get("env", {}), TPUNET_FFI_COLLECTIVES="0")
    res = dp_ranks.run(dp_cell, 12345, 2.0, True, platform="cpu")
    assert res["correct"], res["compared"]
    m = res["metrics"]
    assert m["dcn_bridge_host_s_per_step"]["value"] > 0
    assert m["dcn_bridge_bytes_per_call"]["value"] % 4 == 0
    assert "dcn_bridge_d2h_s_per_step" not in m and "dcn_bridge_h2d_s_per_step" not in m


def test_dp_ranks_over_ffi_counts_no_bridge_call(dp_cell):
    res = dp_ranks.run(dp_cell, 12345, 2.0, True, platform="cpu")
    assert not any(name.startswith("dcn_bridge_") and name != "dcn_bridge_s_per_step"
                   for name in res["metrics"])
