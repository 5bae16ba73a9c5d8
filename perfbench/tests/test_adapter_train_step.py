"""The train_step adapter end to end at a tiny size on the CPU, the harness's
look for a chip skipped: the result line's keys, `correct` true on a sound
run, and `correct` false once for each fault the timed path can have and for
the control (the reference one precision down, put in the program's place);
and the command itself, which refuses a machine without a TPU."""

import os
import subprocess
import sys

import pytest

from perfbench import compare, harness
from perfbench.adapters import _train, train_step
from perfbench.harness import check_line


def test_train_step_sound_run(train_cell):
    res = train_step.run(train_cell, 2 ** 31 + 3, 1.0, False, platform="cpu")
    check_line(res, traced=False)
    assert res["correct"] and set(res["metrics"]) == {"step_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_train_step_traced_run(train_cell):
    res = train_step.run(train_cell, 5, 2.0, True, platform="cpu")
    check_line(res, traced=True)
    assert res["correct"]
    # no TPU plane in a CPU trace: the device readers find nothing and say nothing
    assert "device_idle_share.step" not in res["metrics"]
    assert "train_step_mfu" not in res["metrics"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_train_step_faults_come_out_incorrect(train_cell, fault):
    res = train_step.run(train_cell, 7, 0.5, False, platform="cpu", fault=fault)
    assert not res["correct"], res["compared"]


def test_train_control_fails_the_comparison(train_cell):
    """float32 configuration: the control is the reference in bfloat16."""
    exact = _train.reference_steps(train_cell, 11, "f32")
    control = _train.reference_steps(train_cell, 11, "bf16")
    readings, _ = compare.train(control, exact)
    ok, _ = compare.judge(readings, train_cell["limits"])
    assert not ok, readings
    again, _ = compare.train(_train.reference_steps(train_cell, 11, "f32"), exact)
    assert compare.judge(again, train_cell["limits"])[0]


def test_the_command_refuses_a_cpu():
    """python3 -m perfbench.run on a machine with no TPU: non-zero exit and
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cell in ("mistral7b-train-s8192", "vgg16-dp2-tcp"):
        p = subprocess.run(
            [sys.executable, "-m", "perfbench.run", "--workload", cell, "--seed", "1",
             "--seconds", "1", "--trace", "0"], cwd=harness.ROOT, env=env,
            capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert not any(line.startswith("{") for line in p.stdout.splitlines())
