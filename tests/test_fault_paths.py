"""Fault paths: dead peers, connect retry, and error surfacing into JAX.

The reference's failure model was 108 unwrap-panics and silent hangs
(SURVEY §5, reference nthread:396-401); these tests pin the build's
contract instead: a peer dying mid-collective produces a bounded, typed
error on the survivors — including through the io_callback seam into a
jitted program — and transient rendezvous failures retry with backoff.
"""

from __future__ import annotations

import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from conftest import free_port  # noqa: E402


def _bound_death_detection() -> None:
    """Make peer-death verdicts deterministic under load (the documented
    PR 10/11 flake): a SIGKILLed peer's RST can arrive arbitrarily late on
    a loaded box, and a survivor blocked in recv would sit the full 120s
    test budget waiting for it. Arm the RST-independent detectors the
    failure model already ships — the progress watchdog (zero bytes moved
    for a window -> typed ProgressTimeoutError, classified like a dead
    peer) and short TCP keepalive — in the WORKER processes, before any
    engine exists. The verdict is then bounded at ~20s whether or not the
    kernel ever delivers the RST; which typed error wins the race is
    deliberately unasserted (both are the contract)."""
    os.environ.setdefault("TPUNET_PROGRESS_TIMEOUT_MS", "20000")
    os.environ.setdefault("TPUNET_KEEPALIVE_IDLE_S", "5")
    os.environ.setdefault("TPUNET_KEEPALIVE_INTVL_S", "2")
    os.environ.setdefault("TPUNET_KEEPALIVE_CNT", "3")


def _victim(rank: int, world: int, port: int, q) -> None:
    # Rank 1 starts an allreduce and is SIGKILLed by the parent mid-flight.
    _bound_death_detection()
    from tpunet.collectives import Communicator

    comm = Communicator(f"127.0.0.1:{port}", rank, world)
    comm.barrier()
    arr = np.ones((64 << 20) // 4, np.float32)  # 64 MiB: long enough to die in
    comm.all_reduce(arr)
    q.put((rank, "in the loop"))  # see _kill_mid_collective
    while True:  # loop until killed
        comm.all_reduce(arr)


def _survivor(rank: int, world: int, port: int, q) -> None:
    try:
        _bound_death_detection()
        from tpunet.collectives import Communicator

        comm = Communicator(f"127.0.0.1:{port}", rank, world)
        comm.barrier()
        q.put((rank, "ready"))
        arr = np.ones((64 << 20) // 4, np.float32)
        t0 = time.perf_counter()
        try:
            while True:
                comm.all_reduce(arr)
                if time.perf_counter() - t0 > 120:
                    q.put((rank, "FAIL: no error after peer death"))
                    return
        except RuntimeError as e:
            dt = time.perf_counter() - t0
            q.put((rank, f"OK error after {dt:.1f}s: {str(e)[:80]}"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def _prewiring_victim(rank: int, world: int, port: int, q) -> None:
    # Dies after Init but BEFORE the first allreduce — so the survivor's
    # lazy channel wiring (first collective) must fail with a typed error
    # when it connects to the dead peer's closed listener, never hang.
    # `q` is the victim's OWN queue, not shared with the survivor: a
    # SIGKILL landing between the feeder thread's pipe write and its
    # release of the queue's cross-process write lock would wedge every
    # other writer forever — and on a 1-core box the parent reliably wakes
    # from q.get (the pipe write) BEFORE that release, so kill-after-get
    # hits the window ~half the time. Dedicated queue = no shared lock.
    _bound_death_detection()
    from tpunet.collectives import Communicator

    comm = Communicator(f"127.0.0.1:{port}", rank, world)
    comm.barrier()
    q.put((rank, "ready"))
    time.sleep(600)  # parent SIGKILLs long before this


def _prewiring_survivor(rank: int, world: int, port: int, q, go) -> None:
    try:
        _bound_death_detection()
        os.environ["TPUNET_CONNECT_RETRY_MS"] = "3000"
        from tpunet.collectives import Communicator

        comm = Communicator(f"127.0.0.1:{port}", rank, world)
        comm.barrier()
        q.put((rank, "ready"))
        # Block until the parent confirms the victim is DEAD — a sleep here
        # races: wiring against a still-alive-but-about-to-die victim blocks
        # in accept (its backlog accepts our connect, no reply ever comes)
        # instead of exercising the connect-refused path this test pins.
        go.get(timeout=120)
        arr = np.ones(4096, np.float32)
        t0 = time.perf_counter()
        try:
            comm.iall_reduce(arr).wait()
            q.put((rank, "FAIL: no error from wiring against a dead peer"))
        except RuntimeError as e:
            q.put((rank, f"OK error after {time.perf_counter() - t0:.1f}s: "
                         f"{str(e)[:80]}"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_peer_death_before_channel_wiring_errors_cleanly():
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    vq = ctx.Queue()  # victim-only: see _prewiring_victim on why not shared
    go = ctx.Queue()
    port = free_port()
    surv = ctx.Process(target=_prewiring_survivor, args=(0, 2, port, q, go))
    vict = ctx.Process(target=_prewiring_victim, args=(1, 2, port, vq))
    try:
        surv.start()
        vict.start()
        ready = {q.get(timeout=120)[0], vq.get(timeout=120)[0]}
        assert ready == {0, 1}
        vict.kill()  # before the survivor's first collective wires channels
        vict.join(timeout=30)
        go.put("victim dead")  # release the survivor into channel wiring
        rank, status = q.get(timeout=120)
        surv.join(timeout=30)
        assert rank == 0 and status.startswith("OK error"), status
    finally:
        # A startup failure must not leave the 600s-sleeping victim (or a
        # wedged survivor) blocking pytest exit.
        for p in (surv, vict):
            if p.pid is None:  # start() itself failed: nothing to reap
                continue
            if p.is_alive():
                p.kill()
            p.join(timeout=10)


def _kill_mid_collective(survivor, victim, wait_s: float = 240):
    """Start rank 0 `survivor` and rank 1 `victim`, SIGKILL the victim while
    both are looping over collectives, and return (the survivor's verdict,
    the survivor's process).

    No sleep decides when to kill. The victim reports "in the loop" after
    its first post-warm-up collective has completed — a collective, so the
    survivor completed it too — and from then on both sit in back-to-back
    collectives, so whenever the kill lands a collective is in flight or
    the next one will wait on a dead peer. The victim reports on a queue of
    its OWN (see _prewiring_victim: a SIGKILL inside a shared queue's write
    lock wedges every other writer).

    Under six xdist workers these tests used to lose the survivor's verdict
    for good. That was the engine, not the test: a sender whose one stream
    failed over waited for a NACK its dead peer could no longer send
    (basic_engine.cc NackReaderGone, docs/DESIGN.md "single-stream
    failover" point 5)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    q, vq = ctx.Queue(), ctx.Queue()
    port = free_port()
    surv = ctx.Process(target=survivor, args=(0, 2, port, q))
    vict = ctx.Process(target=victim, args=(1, 2, port, vq))
    try:
        surv.start()
        vict.start()
        assert q.get(timeout=wait_s) == (0, "ready")
        assert vq.get(timeout=wait_s) == (1, "in the loop")
        vict.kill()  # SIGKILL: no goodbye, sockets RST on close
        rank, status = q.get(timeout=wait_s)
        assert rank == 0
        surv.join(timeout=60)
        vict.join(timeout=30)
        return status, surv
    finally:
        for p in (surv, vict):
            if p.pid is not None and p.is_alive():
                p.kill()
                p.join(timeout=10)


def test_peer_death_mid_allreduce_errors_cleanly():
    status, _ = _kill_mid_collective(_survivor, _victim)
    assert status.startswith("OK error"), status


def _jax_survivor(rank: int, world: int, port: int, q) -> None:
    try:
        _bound_death_detection()
        import jax

        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.interop import dcn_psum

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        fn = jax.jit(dcn_psum)
        x = jnp.ones((16 << 20) // 4, jnp.float32)  # 16 MiB
        np.asarray(fn(x))  # warm compile + one good sync
        q.put((rank, "ready"))
        t0 = time.perf_counter()
        try:
            while True:
                np.asarray(fn(x))
                if time.perf_counter() - t0 > 120:
                    q.put((rank, "FAIL: no exception after peer death"))
                    return
        except Exception as e:  # noqa: BLE001 — XlaRuntimeError wraps ours
            q.put((rank, f"OK raised {type(e).__name__} after "
                         f"{time.perf_counter() - t0:.1f}s"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def _jax_victim(rank: int, world: int, port: int, q) -> None:
    _bound_death_detection()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from tpunet import distributed
    from tpunet.interop import dcn_psum

    distributed.initialize(f"127.0.0.1:{port}", rank, world)
    fn = jax.jit(dcn_psum)
    x = jnp.ones((16 << 20) // 4, jnp.float32)
    np.asarray(fn(x))  # warm compile + one good sync
    np.asarray(fn(x))
    q.put((rank, "in the loop"))  # see _kill_mid_collective
    while True:
        np.asarray(fn(x))


def test_peer_death_surfaces_as_jax_exception():
    # The bridge into the jitted program must turn the transport error into
    # a Python exception out of it — not a wedge.
    status, _ = _kill_mid_collective(_jax_survivor, _jax_victim)
    assert status.startswith("OK raised"), status


def _async_survivor(rank: int, world: int, port: int, q) -> None:
    # Nonblocking tickets in flight when the peer dies: the first failing
    # wait raises, the REST are dropped un-waited. The AsyncResult finalizer
    # must quiesce them so process exit doesn't free buffers under the
    # native worker thread (regression: exit-time SIGSEGV).
    try:
        _bound_death_detection()
        from tpunet.collectives import Communicator

        comm = Communicator(f"127.0.0.1:{port}", rank, world)
        comm.barrier()
        q.put((rank, "ready"))
        arr = np.ones((32 << 20) // 4, np.float32)
        t0 = time.perf_counter()
        try:
            while time.perf_counter() - t0 < 120:
                rs = [comm.iall_reduce(arr) for _ in range(3)]
                for r in rs:
                    r.wait()
            q.put((rank, "FAIL: no error after peer death"))
        except RuntimeError:
            q.put((rank, "OK errored"))  # unwaited rs members drop here
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def _async_victim(rank: int, world: int, port: int, q) -> None:
    _bound_death_detection()
    from tpunet.collectives import Communicator

    comm = Communicator(f"127.0.0.1:{port}", rank, world)
    comm.barrier()
    arr = np.ones((32 << 20) // 4, np.float32)
    comm.all_reduce(arr)
    q.put((rank, "in the loop"))  # see _kill_mid_collective
    while True:
        comm.all_reduce(arr)


def test_peer_death_with_unwaited_async_tickets_exits_cleanly():
    status, surv = _kill_mid_collective(_async_survivor, _async_victim)
    assert status == "OK errored", status
    # The regression: survivor used to die with SIGSEGV (-11) at exit.
    assert surv.exitcode == 0, f"survivor exitcode {surv.exitcode}"


def _ipv4_handle(port: int) -> bytes:
    # sockaddr_in marshaled as the 64-byte wire handle: family (host order),
    # BE port, 127.0.0.1.
    return (struct.pack("=H", socket.AF_INET) + struct.pack("!H", port)
            + socket.inet_aton("127.0.0.1")).ljust(64, b"\0")


def test_connect_retries_until_listener_appears():
    # Nothing listens at connect() time; a plain acceptor shows up ~1s
    # later. The engine's backoff retry must bridge the gap.
    from tpunet.transport import Net

    port = free_port()
    accepted = {}

    def late_listener():
        time.sleep(1.0)
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", port))
        s.listen(16)
        conns = []
        s.settimeout(20)
        try:
            while True:
                c, _ = s.accept()
                conns.append(c)
                accepted["n"] = len(conns)
        except TimeoutError:
            pass
        finally:
            for c in conns:
                c.close()
            s.close()

    th = threading.Thread(target=late_listener, daemon=True)
    th.start()
    os.environ["TPUNET_CONNECT_RETRY_MS"] = "15000"
    try:
        with Net() as net:
            t0 = time.perf_counter()
            sc = net.connect(_ipv4_handle(port))
            dt = time.perf_counter() - t0
            assert dt >= 0.8, f"connected before the listener existed? {dt}"
            sc.close()
    finally:
        os.environ.pop("TPUNET_CONNECT_RETRY_MS", None)
    assert accepted.get("n", 0) >= 1


def test_connect_fails_cleanly_when_nothing_ever_listens():
    from tpunet.transport import Net

    os.environ["TPUNET_CONNECT_RETRY_MS"] = "1000"
    try:
        with Net() as net:
            t0 = time.perf_counter()
            with pytest.raises(RuntimeError, match="connect"):
                net.connect(_ipv4_handle(free_port()))
            assert time.perf_counter() - t0 < 10
    finally:
        os.environ.pop("TPUNET_CONNECT_RETRY_MS", None)
