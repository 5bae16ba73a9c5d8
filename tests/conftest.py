"""Test harness config.

JAX tests run on a virtual 8-device CPU mesh (no TPU pod in CI) — the env
must be set before the first jax import anywhere in the test process.
"""

import os

# The suite runs on the CPU, whatever the environment names: forced, not
# setdefault.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# And in the config, for a process that imported jax before this file.
# Guarded so the non-JAX tests (transport/collectives) still run without jax.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:  # pragma: no cover
    pass


import socket  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _flightrec_dumps_to_tmp(tmp_path, monkeypatch):
    """Route flight-recorder verdict dumps through the test's tmp dir.

    Chaos/CRC tests trip DumpOnVerdict in the native layer, whose fallback
    dump path is the CWD — which under pytest is the repo root. The dedicated
    TPUNET_FLIGHTREC_DIR knob redirects ONLY the dump path (unlike
    TPUNET_TRACE_DIR it does not enable span tracing), and spawned worker
    processes inherit it through the env."""
    monkeypatch.setenv("TPUNET_FLIGHTREC_DIR", str(tmp_path))


def free_port() -> int:
    """Shared helper: an ephemeral 127.0.0.1 port for bootstrap coordinators."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_spawn_workers(target, world: int, timeout: float = 180.0, extra_args=()):
    """Spawn `world` processes running target(rank, world, port, queue, *extra)
    and assert every rank reports 'OK'. Shared by the multiprocess suites."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    port = free_port()
    procs = [
        ctx.Process(target=target, args=(r, world, port, q) + tuple(extra_args))
        for r in range(world)
    ]
    for p in procs:
        p.start()
    results = {}
    try:
        for _ in range(world):
            rank, status = q.get(timeout=timeout)
            results[rank] = status
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
    assert all(v == "OK" for v in results.values()), f"worker failures: {results}"
    assert len(results) == world
