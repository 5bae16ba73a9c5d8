"""chip_smoke.py off the chip: that it refuses to run there, and that its
phases are wired right, at a tiny size on the CPU (the Pallas interpreter
for the kernels, so the kernel counts are zero; four virtual devices for the
four-chip path). What only the chip can show — Mosaic, memory, times — is
tests/test_chip_compile.py's and the chip run's."""

import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import pytest

import chip_smoke
from chip_smoke import FourChipSizes, Peer, Sizes

ROOT = Path(chip_smoke.__file__).parent

TINY = Sizes(
    model=dict(vocab=256, d_model=64, n_layers=2, n_heads=4, d_ff=128),
    dtype="float32", train_kernels=0, decode_kernels=0,
    batch=2, seq=128, train_steps=3,
    kv_heads=2, dec_batch=2, prompt=128, new=4,
    serve_slots=2, serve_max_len=48, serve_prompts=(32, 40, 21),
    native_bytes=1 << 20, psum_bytes=(1 << 16,), dcn_steps=2,
    chain_dim=256, chain_len=64,
)
TINY_FOUR = FourChipSizes(
    model=TINY.model, dtype="float32",
    tp_batch=4, tp_seq=32, sp_batch=1, sp_seq=128, sp_ref_kernels=0,
    psum_elems=1 << 10, loss_rtol=1e-4, spread=1.5,
)


@pytest.fixture
def built_once(monkeypatch):
    """chip_smoke forces a clean build of the library. Here other test
    processes have that file loaded, so the build is left to decide."""
    from tpunet import _native

    build = _native.build_native
    monkeypatch.setattr(_native, "build_native", lambda force=False: build())


@pytest.fixture
def peer():
    p = Peer()
    yield p
    chip_smoke.stop_children()


@pytest.fixture
def io_callback_bridge(monkeypatch):
    """The chip's bridge under every dcn_* call, not the CPU's FFI one."""
    import tpunet.interop  # noqa: F401

    monkeypatch.setattr(sys.modules["tpunet.interop"], "_ffi_available",
                        lambda: False)


@pytest.fixture(scope="module")
def train_result():
    return chip_smoke.phase_train(TINY)


def test_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_phase_native(built_once):
    out = chip_smoke.phase_native(TINY)
    assert out["allreduce_busbw_GBps"] > 0
    assert Path(out["library"]).name == "libtpunet.so"
    assert not chip_smoke._children  # both ranks were stopped


def test_phase_train(train_result):
    assert train_result["kernels"] == 0
    assert train_result["losses"][-1] < train_result["losses"][0]
    assert len(train_result["step_s"]) == TINY.train_steps - 1
    sync = train_result["sync"]
    assert sync["block_until_ready_s"] >= 0.5 * sync["host_transfer_s"]


def test_train_without_its_kernels_is_refused():
    """What main() would meet if JAX came up on the CPU after all, or if
    flash_attention took its einsum path: the interpreter's program holds
    no tpu_custom_call, and the phase says so instead of finishing."""
    with pytest.raises(RuntimeError, match="holds 0 tpu_custom_call"):
        chip_smoke.phase_train(replace(TINY, train_kernels=8))


def test_phase_decode_serve():
    out = chip_smoke.phase_decode_serve(TINY)
    assert [(r["prompt"], r["new"]) for r in out["serve_requests"]] == [
        (32, 16), (40, 8), (21, 27)]
    # in f32 on the CPU the server and generate() agree to the last token
    assert all(r["equals_generate_until"] == r["new"]
               for r in out["serve_requests"])
    assert out["serve_stats"]["prefills"] >= 2  # three requests, two slots


def test_serve_token_off_the_reference_is_refused(monkeypatch):
    from tpunet.models import BatchServer

    run = BatchServer.run

    def one_wrong_token(self, **kw):
        answers = run(self, **kw)
        first = min(answers)
        answers[first] = answers[first].copy()
        answers[first][5] = (answers[first][5] + 97) % TINY.model["vocab"]
        return answers

    monkeypatch.setattr(BatchServer, "run", one_wrong_token)
    with pytest.raises(RuntimeError, match="generated token 5 of 16"):
        chip_smoke.phase_decode_serve(TINY)


@pytest.mark.parametrize("chunk_bytes", [None, 1 << 16], ids=["whole", "chunked"])
def test_phase_dcn(built_once, peer, io_callback_bridge, train_result, chunk_bytes,
                   monkeypatch):
    """chunked: the cross-host step's vector crosses in several chunks, as at
    the chip's size, and the transport-only peer is told each one's length."""
    from tpunet import _native, interop, telemetry

    if chunk_bytes:
        monkeypatch.setattr(interop, "_CHUNK_BYTES", chunk_bytes)
    telemetry.reset()
    out = chip_smoke.phase_dcn(TINY, peer, _native.build_native(),
                               train_result["losses"][0])
    chunks = sum(telemetry.metrics()["tpunet_bridge_chunks_total"].values())
    exchanges = len(out["cross_host_losses"])
    assert chunks == exchanges * len(interop.boundary_chunks(
        out["cross_host_allreduce_bytes"] // 4, 4, 2))
    assert (chunks > exchanges) == bool(chunk_bytes)
    assert out["world_size"] == 2
    assert [p["dtype"] for p in out["psum"]] == ["float32", "bfloat16"]
    assert out["cross_host_losses"][0] == train_result["losses"][0]


def test_dcn_with_its_peer_killed_fails(built_once, peer, io_callback_bridge):
    from tpunet import _native, distributed

    peer.proc.kill()
    try:
        with pytest.raises((RuntimeError, OSError)):
            chip_smoke.phase_dcn(TINY, peer, _native.build_native(), 0.0)
    finally:
        distributed.finalize()


def test_a_failed_phase_fails_the_run(capsys):
    ran = []

    def boom():
        raise RuntimeError("kernel made to fail")

    ok = chip_smoke.run_phases([("first", boom),
                                ("second", lambda: ran.append(1) or {"phase": "second"})])
    assert ok is False and ran == [1]  # reported, and not passed over
    lines = capsys.readouterr().out.splitlines()
    assert '"ok": false' in lines[0] and "kernel made to fail" in lines[0]


def test_four_chips_on_virtual_devices(built_once, peer, io_callback_bridge,
                                       monkeypatch):
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(chip_smoke, "memory", lambda stat: [1, 1, 1, 1])
    assert chip_smoke.run_four_chips(TINY_FOUR, peer, jax.devices()[:4])


@pytest.mark.parametrize("preset", [None, "/some/where/else"])
def test_compile_cache_is_placed_from_outside(preset):
    """Set: nothing is set in code, JAX reads the variable itself. Unset: the
    fixed path under the checkout, and children inherit it."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if preset:
        env["JAX_COMPILATION_CACHE_DIR"] = preset
    code = ("import os; from benchmarks import place_compile_cache; "
            "d = place_compile_cache(); import jax; "
            "print(d); print(jax.config.jax_compilation_cache_dir); "
            "print(os.environ['JAX_COMPILATION_CACHE_DIR'])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [preset or str(ROOT / ".jax_cache")] * 3


def test_kernel_smoke_exits_nonzero_on_a_failed_kernel(monkeypatch, capsys):
    from benchmarks import kernel_smoke

    ok = {k: "ok" for k in kernel_smoke.KERNELS}
    monkeypatch.setattr(kernel_smoke, "run_smoke",
                        lambda: {**ok, "flash_gqa_bwd": "parity 3.1e-01"})
    with pytest.raises(SystemExit) as e:
        kernel_smoke.main(["--platform", "cpu"])
    assert e.value.code not in (0, None) and "flash_gqa_bwd" in str(e.value.code)
    assert '"flash_gqa_bwd": "parity 3.1e-01"' in capsys.readouterr().out
    monkeypatch.setattr(kernel_smoke, "run_smoke", lambda: ok)
    kernel_smoke.main(["--platform", "cpu"])  # all ok: returns
