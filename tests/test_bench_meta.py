"""Unit tests for benchmark metadata helpers (no hardware needed)."""

import pytest

from benchmarks.tpu_headline import PEAK_FLOPS, _peak_for


def test_peak_exact_known_kinds():
    assert _peak_for("TPU v4") == 275e12
    assert _peak_for("TPU v5 lite") == 197e12
    assert _peak_for("TPU v5p") == 459e12
    assert _peak_for("TPU v6 lite") == 918e12
    assert _peak_for("TPU v6e") == 918e12
    assert _peak_for("TPU v3") == 123e12 / 2
    assert _peak_for("TPU v2") == 45e12 / 2


def test_peak_lookup_is_exact():
    # The kind exactly as JAX reports it: no case folding, no prefix or
    # suffix stripping (an older runtime's "TPU v5 lite0" is another kind).
    for kind in ("tpu v5p", "  TPU V4 ", "v5p", "TPU v5 lite0"):
        with pytest.raises(KeyError, match="no peak FLOP/s recorded"):
            _peak_for(kind)


def test_peak_unknown_is_an_error():
    # Unknown kinds must NOT substring-match onto a wrong row (the round-2
    # failure mode: "v5" caught any future v5 variant), and must not pass
    # as "mfu: null" either: a device that is not in the table is an error.
    for kind in ("TPU v7x", "TPU v5 mega", "gpu a100", "cpu"):
        with pytest.raises(KeyError, match=kind):
            _peak_for(kind)


def test_table_values_positive():
    assert all(v > 0 for v in PEAK_FLOPS.values())


def _tool(returncode=0, stdout=None, stderr=""):
    """A subprocess.run stand-in for one device-tier tool."""
    import json

    class _P:
        pass

    _P.returncode, _P.stderr = returncode, stderr
    _P.stdout = json.dumps(stdout) if stdout is not None else ""
    return lambda *a, **k: _P


def test_model_tier_runs_on_the_chip_or_fails():
    import unittest.mock as mock

    import bench

    ok = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
          "tokens_per_s": 1.0}
    calls = []

    def record(cmd, **kw):
        calls.append(cmd)
        return _tool(stdout=ok)()

    with mock.patch("subprocess.run", side_effect=record):
        assert bench._model_tier() == ok
    # flash on the chip, with no way to ask for anything else
    assert calls[0][1:] == ["-m", "benchmarks.tpu_headline"]

    # The tool failed (no chip, a kernel refused, an OOM): the bench ends.
    with mock.patch("subprocess.run",
                    side_effect=_tool(1, stderr="this run needs a TPU")):
        with pytest.raises(SystemExit, match="needs a TPU"):
            bench._model_tier()
    # A kernel smoke that is not all "ok" exits non-zero itself: same end,
    # no demotion to reference attention.
    with mock.patch("subprocess.run",
                    side_effect=_tool(1, stderr="kernel smoke failed")):
        with pytest.raises(SystemExit, match="kernel smoke failed"):
            bench._kernel_smoke()
    # A line that came from another device is refused, not relabelled.
    with mock.patch("subprocess.run",
                    side_effect=_tool(stdout={**ok, "platform": "cpu"})):
        with pytest.raises(SystemExit, match="not a TPU"):
            bench._model_tier()


def test_finalize_drains_pending_async():
    from conftest import free_port

    from tpunet import distributed
    from tpunet.interop import (
        _register_pending,
        dcn_async_stats,
        dcn_async_stats_reset,
    )
    import numpy as np

    dcn_async_stats_reset()
    distributed.finalize()
    comm = distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    _register_pending(comm, comm.iall_reduce(np.ones(16, np.float32)))
    assert dcn_async_stats()["in_flight"] == 1
    distributed.finalize()  # must drop the stale entry, not leak it
    assert dcn_async_stats()["in_flight"] == 0


def test_decode_bench_cli(capsys):
    import json

    from benchmarks.decode_bench import main as decode_main

    decode_main([
        "--platform", "cpu", "--d", "64", "--layers", "2", "--heads", "4", "--ff", "128",
        "--vocab", "256", "--batch", "2", "--prompt", "8", "--new", "4",
        "--kv-heads", "2", "--iters", "1",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["decode_tok_s"] > 0
    assert out["kv_heads"] == 2
    assert (out["platform"], out["device_kind"], out["device_count"]) == (
        "cpu", "cpu", 8)


def test_decode_bench_window(capsys):
    import json

    from benchmarks.decode_bench import main as decode_main

    decode_main([
        "--platform", "cpu", "--d", "64", "--layers", "2", "--heads", "4", "--ff", "128",
        "--vocab", "256", "--batch", "2", "--prompt", "8", "--new", "4",
        "--window", "6", "--iters", "1",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["decode_tok_s"] > 0
    assert out["window"] == 6


def test_decode_bench_speculative(capsys):
    import json

    from benchmarks.decode_bench import main as decode_main

    decode_main([
        "--platform", "cpu", "--d", "64", "--layers", "2", "--heads", "4", "--ff", "128",
        "--vocab", "256", "--batch", "2", "--prompt", "8", "--new", "6",
        "--iters", "1", "--spec-gamma", "2", "--draft-layers", "1",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    spec = out["speculative"]
    assert spec["gamma"] == 2 and spec["draft_layers"] == 1
    assert spec["spec_tok_s_floor"] > 0
    # The ceiling commits gamma+1 tokens per round by construction.
    assert spec["spec_tok_s_ceiling"] >= spec["spec_tok_s_floor"]
    assert 0.0 <= spec["accept_rate_floor"] <= 1.0
    assert spec["rounds"] >= 1


def test_decode_bench_quant_and_quant_draft(capsys):
    import json

    from benchmarks.decode_bench import main as decode_main

    decode_main([
        "--platform", "cpu", "--d", "64", "--layers", "2", "--heads", "4", "--ff", "128",
        "--vocab", "256", "--batch", "2", "--prompt", "8", "--new", "6",
        "--iters", "1", "--quant", "int8", "--spec-gamma", "2",
        "--spec-draft", "quant",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["quant"]["dtype"] == "int8"
    assert out["quant"]["decode_tok_s"] > 0
    spec = out["speculative"]
    assert spec["draft"] == "quant" and "draft_layers" not in spec
    assert "accept_rate" in spec and "accept_rate_floor" not in spec
    assert spec["spec_tok_s"] > 0 and spec["vs_plain"] > 0


def test_mfu_attribution_cpu_smoke(capsys):
    import json

    from benchmarks.mfu_attribution import main as attr_main

    attr_main(["--platform", "cpu", "--d", "64", "--layers", "2", "--ff", "128", "--heads", "4",
               "--vocab", "256", "--batch", "2", "--seq", "128", "--fp32",
               "--iters", "2"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(out["segments"]) == {"attn", "qkvo", "ffn", "xent", "adamw"}
    assert out["full_step_ms"] > 0
    # The per-segment model must reconcile with the measured step to
    # first order even on CPU (no remat there, so expected ~= blocks
    # fwd+bwd + xent + opt).
    assert out["expected_full_ms"] > 0


def test_kernel_smoke_window_entries_cpu():
    from benchmarks.kernel_smoke import run_smoke

    out = run_smoke()
    for k in ("flash_fwd", "flash_bwd", "flash_gqa_fwd", "flash_gqa_bwd",
              "flash_window_fwd", "flash_window_bwd",
              "flash_gqa_window_fwd", "flash_gqa_window_bwd"):
        assert out[k] == "ok", f"{k}: {out[k]}"


def test_decode_tier_runs_on_the_chip_or_fails():
    import unittest.mock as mock

    import bench

    with mock.patch("subprocess.run", side_effect=_tool(
            stdout={"platform": "tpu", "decode_tok_s": 9})):
        assert bench._decode_tier()["decode_tok_s"] == 9

    # decode_bench on another device: the bench fails, the datapoint is not
    # dropped in silence and the run does not carry on without it.
    with mock.patch("subprocess.run", side_effect=_tool(
            stdout={"platform": "cpu", "decode_tok_s": 9})):
        with pytest.raises(SystemExit, match="not a TPU"):
            bench._decode_tier()
    with mock.patch("subprocess.run", side_effect=_tool(1, stderr="boom")):
        with pytest.raises(SystemExit, match="boom"):
            bench._decode_tier()
