"""Intra-host shared-memory transport (TPUNET_SHM=1, cpp/src/shm_engine.cc).

Host-locality unit tests (host-id derivation, the TPUNET_HOST_ID fake-host
override, Config knob registration), 2-process SHM loopback transfers with
counter proof that the payload rode the ring segment and ZERO TCP data
bytes, and the forced-split paths (TPUNET_SHM=0 / mismatched fake hosts)
falling back to TCP transparently.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import free_port  # noqa: F401  (shared harness import path)

SWEEP = [0, 8, 777, 1 << 20, (1 << 24) + 13]  # wrap-exercising sizes
SWEEP_SMALL = [0, 8, 777, 1 << 20]  # routing-proof lanes skip the wrap size


def _host_id_in_subprocess(env: dict) -> int:
    """HostId() as seen by a fresh process (the id is cached per process, so
    override tests need isolation)."""
    code = (
        "from tpunet import _native; lib = _native.load(); "
        "print(lib.tpunet_c_host_id())"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, **env},
        capture_output=True, text=True, check=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
    )
    return int(out.stdout.strip().splitlines()[-1])


def test_host_id_stable_and_nonzero():
    """Derivation: boot-id/hostname hash — stable across processes on one
    box, never zero (0 would read as 'no identity' in the handshake)."""
    env = {"TPUNET_HOST_ID": ""}
    a = _host_id_in_subprocess(env)
    b = _host_id_in_subprocess(env)
    assert a != 0
    assert a == b, "host id must be identical for two processes on one host"


def test_host_id_override_splits_hosts():
    """TPUNET_HOST_ID is the fake-host knob: different strings hash to
    different ids (testable multi-'host' topologies on one box), equal
    strings to equal ids, and any override differs from the natural id."""
    natural = _host_id_in_subprocess({"TPUNET_HOST_ID": ""})
    ha = _host_id_in_subprocess({"TPUNET_HOST_ID": "hostA"})
    ha2 = _host_id_in_subprocess({"TPUNET_HOST_ID": "hostA"})
    hb = _host_id_in_subprocess({"TPUNET_HOST_ID": "hostB"})
    assert ha == ha2
    assert ha != hb
    assert ha != natural and hb != natural
    assert ha != 0 and hb != 0


def test_config_registers_shm_knobs(monkeypatch):
    from tpunet.config import Config

    monkeypatch.setenv("TPUNET_SHM", "1")
    monkeypatch.setenv("TPUNET_HOST_ID", "boxA")
    monkeypatch.setenv("TPUNET_SHM_RING_BYTES", str(1 << 20))
    cfg = Config.from_env()
    assert cfg.shm is True
    assert cfg.host_id == "boxA"
    assert cfg.shm_ring_bytes == 1 << 20
    # Range validation names the offending var (PR-1 validator stance).
    monkeypatch.setenv("TPUNET_SHM_RING_BYTES", "1024")  # < 64K floor
    with pytest.raises(ValueError, match="TPUNET_SHM_RING_BYTES"):
        Config.from_env()
    monkeypatch.setenv("TPUNET_SHM_RING_BYTES", str(1 << 31))  # > 1G cap
    with pytest.raises(ValueError, match="TPUNET_SHM_RING_BYTES"):
        Config.from_env()


# ---------------------------------------------------------------------------
# 2-process loopback transfers.


def _receiver(conn, env: dict, sizes: list) -> None:
    os.environ.update(env)
    from tpunet import telemetry
    from tpunet.transport import Net

    net = Net()
    listen = net.listen(0)
    conn.send(bytes(listen.handle))
    rc = listen.accept()
    ok = True
    for i, size in enumerate(sizes):
        buf = np.zeros(size + 64, dtype=np.uint8)  # oversized on purpose
        got = rc.recv(buf, timeout=60)
        exp = np.arange(size, dtype=np.uint64).astype(np.uint8)
        if got != size or not np.array_equal(buf[:size], exp):
            ok = False
            break
    m = telemetry.metrics()
    shm_rx = sum(int(v) for k, v in m.get("tpunet_shm_bytes_total", {}).items()
                 if telemetry.labels(k)["dir"] == "rx")
    tcp_rx = sum(int(v) for v in m.get("tpunet_stream_rx_bytes", {}).values())
    conn.send(("OK" if ok else "CORRUPT", shm_rx, tcp_rx))
    rc.close()
    listen.close()
    net.close()


def _sender(conn, env: dict, sizes: list) -> None:
    os.environ.update(env)
    from tpunet import telemetry
    from tpunet.transport import Net

    net = Net()
    sc = net.connect(conn.recv())
    for size in sizes:
        data = np.arange(size, dtype=np.uint64).astype(np.uint8)
        assert sc.send(data, timeout=60) == size
    m = telemetry.metrics()
    shm_tx = sum(int(v) for k, v in m.get("tpunet_shm_bytes_total", {}).items()
                 if telemetry.labels(k)["dir"] == "tx")
    wakeups = sum(int(v) for v in m.get("tpunet_shm_wakeups_total", {}).values())
    conn.send(("OK", shm_tx, wakeups))
    sc.close()
    net.close()


def _run_pair(env_recv: dict, env_send: dict, sizes: list = SWEEP):
    ctx = mp.get_context("spawn")
    pr, cr = ctx.Pipe()
    ps, cs = ctx.Pipe()
    r = ctx.Process(target=_receiver, args=(cr, env_recv, sizes))
    s = ctx.Process(target=_sender, args=(cs, env_send, sizes))
    r.start()
    s.start()
    try:
        handle = pr.recv()
        ps.send(handle)
        recv_res = pr.recv()
        send_res = ps.recv()
    finally:
        for p in (r, s):
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
    assert recv_res[0] == "OK", recv_res
    assert send_res[0] == "OK", send_res
    return recv_res, send_res


TOTAL = sum(SWEEP)
TOTAL_SMALL = sum(SWEEP_SMALL)


@pytest.mark.parametrize("crc", [0, 1])
def test_shm_loopback_sweep_rides_the_ring(crc):
    """Same host, TPUNET_SHM=1: every payload byte moves through the ring
    segment (tpunet_shm_bytes_total == payload total on both sides), the
    TCP data-stream byte counters stay at EXACTLY zero, CRC trailers
    compose (sizes cover zero-byte, sub-chunk, multi-chunk, and ring-wrap
    transfers — the posted recv buffers are oversized on purpose, pinning
    the LEN-frame semantics), and the futex waiter-count gate keeps the
    wakeup count streaming-scale (far under one wake per chunk — the
    ring's syscalls/MiB analogue, reported by engine_p2p --engines SHM)."""
    env = {"TPUNET_SHM": "1", "TPUNET_CRC": str(crc)}
    (_, shm_rx, tcp_rx), (_, shm_tx, wakeups) = _run_pair(env, env)
    assert shm_rx == TOTAL, (shm_rx, TOTAL)
    assert shm_tx == TOTAL, (shm_tx, TOTAL)
    assert tcp_rx == 0, f"intra-host transfer moved {tcp_rx} TCP bytes"
    assert wakeups <= 2 * (TOTAL // (1 << 20) + len(SWEEP)), wakeups


def test_shm_fake_host_split_falls_back_to_tcp():
    """Forced split: mismatched TPUNET_HOST_ID values nack the segment
    handshake and the pair runs over TCP transparently — zero SHM bytes,
    full payload on the TCP counters, same data integrity."""
    (_, shm_rx, tcp_rx), (_, shm_tx, _) = _run_pair(
        {"TPUNET_SHM": "1", "TPUNET_HOST_ID": "hostA"},
        {"TPUNET_SHM": "1", "TPUNET_HOST_ID": "hostB"},
        sizes=SWEEP_SMALL,
    )
    assert shm_rx == 0 and shm_tx == 0
    assert tcp_rx == TOTAL_SMALL, (tcp_rx, TOTAL_SMALL)


def test_shm_disabled_is_plain_tcp():
    """TPUNET_SHM=0 (the default): nothing touches the SHM counters and the
    existing TCP path is byte-identical to a pre-SHM build."""
    env = {"TPUNET_SHM": "0"}
    (_, shm_rx, tcp_rx), (_, shm_tx, _) = _run_pair(env, env, sizes=SWEEP_SMALL)
    assert shm_rx == 0 and shm_tx == 0
    assert tcp_rx == TOTAL_SMALL


# ---------------------------------------------------------------------------
# A sender that is done and closes before the receiver has accepted.


def _late_receiver(conn, env: dict, sizes: list) -> None:
    os.environ.update(env)
    import time

    from tpunet.transport import Net

    net = Net()
    listen = net.listen(0)
    conn.send(bytes(listen.handle))
    assert conn.recv() == "SENT"  # every send has completed on the other side
    time.sleep(0.3)  # ... and its close is under way
    try:
        rc = listen.accept()
        for size in sizes:
            buf = np.zeros(size, dtype=np.uint8)
            got = rc.recv(buf, timeout=30)
            exp = np.arange(size, dtype=np.uint64).astype(np.uint8)
            assert got == size and np.array_equal(buf, exp), (size, got)
        rc.close()
        conn.send("OK")
    except Exception as e:  # noqa: BLE001
        conn.send(f"FAIL: {type(e).__name__}: {e}")
    listen.close()
    net.close()


def _early_closer(conn, env: dict, sizes: list) -> None:
    os.environ.update(env)
    from tpunet.transport import Net

    net = Net()
    sc = net.connect(conn.recv())
    for size in sizes:
        data = np.arange(size, dtype=np.uint64).astype(np.uint8)
        assert sc.send(data, timeout=30) == size
    conn.send("SENT")
    sc.close()
    net.close()
    conn.send("CLOSED")


@pytest.mark.parametrize("recv_host", ["one", "other"])
def test_shm_close_before_accept_still_delivers(recv_host):
    """Sends that completed into the ring before the handshake verdict are
    the kernel-buffer analogue: a sender that then closes, before the
    receiver is even inside accept(), still delivers them: over the ring
    when the receiver acks (same host), replayed over ctrl when it nacks
    (the fake-host split). Closing used to drop the deferred LEN frames,
    and a first pipeline stage that finished early took its microbatches
    with it (tests/test_workloads.py, under load)."""
    sizes = [8, 4096, 4096, 1 << 16]
    env_s = {"TPUNET_SHM": "1", "TPUNET_HOST_ID": "one"}
    env_r = {"TPUNET_SHM": "1", "TPUNET_HOST_ID": recv_host}
    ctx = mp.get_context("spawn")
    pr, cr = ctx.Pipe()
    ps, cs = ctx.Pipe()
    r = ctx.Process(target=_late_receiver, args=(cr, env_r, sizes))
    s = ctx.Process(target=_early_closer, args=(cs, env_s, sizes))
    r.start()
    s.start()
    try:
        ps.send(pr.recv())
        assert ps.poll(60) and ps.recv() == "SENT"
        pr.send("SENT")
        assert pr.poll(60)
        recv_res = pr.recv()
        assert ps.poll(60) and ps.recv() == "CLOSED"
    finally:
        for p in (r, s):
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
    assert recv_res == "OK", recv_res


# ---------------------------------------------------------------------------
# Ring reduces on the SHM comms: the receive thread folds each chunk into the
# accumulator as it lands (Net::irecv_reduce), and the result must be the TCP
# ring's to the byte.

MIB = 1 << 20


def _ring_cases(world: int) -> list:
    """(collective, dtype, op, count, in place): every dtype x op on odd
    counts, then slices under, at and over the ring's 8 MiB pipeline chunk.
    `count` is the all_reduce's element count, or reduce_scatter's per rank."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    cases = []
    for i, (dt, op) in enumerate((dt, op) for dt in (np.float32, bf16, np.int32)
                                 for op in ("sum", "max", "prod")):
        cases.append(("all_reduce", dt, op, world * 5003 + 3, i % 2 == 0))
        cases.append(("reduce_scatter", dt, op, 4099, False))
    cases += [
        ("all_reduce", np.float32, "sum", world * (MIB // 4 + 1) + 1, False),
        ("all_reduce", np.float32, "prod", world * (2 * MIB), True),
        ("all_reduce", bf16, "max", world * (4 * MIB), False),
        ("all_reduce", np.int32, "sum", world * (3 * MIB) + 5, True),
        ("reduce_scatter", np.float32, "sum", 2 * MIB, False),
        ("reduce_scatter", bf16, "prod", 5 * MIB + 1, False),
    ]
    return cases


def _ring_input(rank: int, case_idx: int, dt, op: str, n: int) -> np.ndarray:
    rng = np.random.default_rng(1000 * case_idx + rank)
    if np.dtype(dt) == np.int32:
        lo, hi = (-3, 4) if op == "prod" else (-1000, 1000)
        return rng.integers(lo, hi, n, dtype=np.int32)
    x = rng.standard_normal(n, dtype=np.float32)
    return (1 + x / 64 if op == "prod" else x).astype(dt)


def _ring_reduce_bytes(coll: str, count: int, world: int, rank: int, esize: int) -> int:
    """Bytes this rank's ring reduces: the slices it receives in the
    reduce-scatter half (cpp/src/schedule_ring.cc)."""
    if coll == "reduce_scatter":
        return (world - 1) * count * esize
    vr = (rank + world - 1) % world
    ridx = [(vr - s - 1) % world for s in range(world - 1)]
    return sum((count * (r + 1) // world - count * r // world) * esize for r in ridx)


def _ring_worker(rank: int, world: int, port: int, q, mode: str) -> None:
    try:
        import hashlib

        os.environ.update(TPUNET_SHM_RING_BYTES="65536", TPUNET_RANK=str(rank),
                          TPUNET_CRC="1" if mode == "crc" else "0")
        if mode == "oddchunk":
            # 10001-byte chunks cut elements: their bytes wait in the bounce.
            os.environ["TPUNET_MIN_CHUNKSIZE"] = "10001"
        from tpunet import telemetry, transport
        from tpunet.collectives import Communicator

        comms = {}
        for shm, p in (("1", port), ("0", port + 1)):
            os.environ["TPUNET_SHM"] = shm
            comms[shm] = Communicator(f"127.0.0.1:{p}", rank, world, algo="ring")
        if mode == "close" and rank == 1:
            # The segment to rank 2 fails over to ctrl TCP mid-message.
            transport.fault_inject("stream=0:side=send:after_bytes=3M:action=close")
        digests, failovers = {}, 0
        for shm in ("1", "0"):
            for i, (coll, dt, op, count, inplace) in enumerate(_ring_cases(world)):
                n = count * world if coll == "reduce_scatter" else count
                x = _ring_input(rank, i, dt, op, n)
                telemetry.reset()
                if coll == "reduce_scatter":
                    out = comms[shm].reduce_scatter(x, op=op)
                else:
                    out = comms[shm].all_reduce(x, op=op, inplace=inplace)
                m = telemetry.metrics()
                landed = sum(m["tpunet_shm_reduce_bytes_total"].values())
                reduced = sum(m["tpunet_reduce_bytes_total"].values())
                failovers += sum(m["tpunet_stream_failovers_total"].values())
                want = _ring_reduce_bytes(coll, count, world, rank, x.itemsize)
                assert reduced == want, (shm, i, reduced, want)
                assert landed == (want if shm == "1" else 0), (shm, i, landed, want)
                digests.setdefault(i, []).append(hashlib.sha256(out.tobytes()).hexdigest())
            transport.fault_clear()
        differ = [i for i, (a, b) in digests.items() if a != b]
        assert not differ, f"SHM and TCP rings differ in cases {differ}"
        assert failovers == (mode == "close" and rank == 1), failovers
        for c in comms.values():
            c.close()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


@pytest.mark.parametrize("world,mode", [(2, "plain"), (3, "plain"), (4, "plain"),
                                        (3, "crc"), (3, "close"), (3, "oddchunk")])
def test_shm_ring_reduce_lands_as_tcp_does(world, mode):
    """all_reduce and reduce_scatter over the SHM ring, whose receive thread
    reduces each chunk as it lands, are byte-identical to the same calls over
    TCP: f32, bf16 and i32 under sum, max and prod, in place and out of place,
    odd counts and slices under, at and over the 8 MiB pipeline chunk, on a
    64 KiB ring whose extents wrap. tpunet_shm_reduce_bytes_total counts
    every byte the reduce-scatter half reduced on SHM, and none on TCP. The
    same holds with CRC trailers (every chunk through the bounce buffer),
    with the segment failed over to ctrl TCP mid-message, and with a chunk
    size that cuts elements in two."""
    from conftest import run_spawn_workers

    run_spawn_workers(_ring_worker, world, timeout=240, extra_args=(mode,))
