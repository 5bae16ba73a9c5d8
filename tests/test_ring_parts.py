"""Part spans inside the native collective phases (cpp/src/coll_comm.h
PhaseSpan::Part): coll.wait_peer, coll.wait_wire and coll.reduce split each
rs.k / ag.k of a two-rank loopback ring, on each engine that carries the
benchmark's rings (docs/DESIGN.md 6c); on SHM comms the reduce is inside
the receive, so there is no coll.reduce."""

from __future__ import annotations

import glob
import json
import os
import sys
import time

import numpy as np
import pytest

from conftest import run_spawn_workers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("coll.wait_peer", "coll.wait_wire", "coll.reduce")
ENGINES = {
    "basic": {"TPUNET_IMPLEMENT": "BASIC", "TPUNET_SHM": "0"},
    "epoll": {"TPUNET_IMPLEMENT": "EPOLL", "TPUNET_SHM": "0"},
    "shm": {"TPUNET_IMPLEMENT": "BASIC", "TPUNET_SHM": "1"},
}
# The SHM engine's receive thread reduces each chunk as it lands
# (Net::irecv_reduce): on its comms the reduce lies inside coll.wait_wire.
ENGINE_PARTS = {"basic": set(PARTS), "epoll": set(PARTS),
                "shm": {"coll.wait_peer", "coll.wait_wire"}}
N = 16 << 20  # 64 MiB of f32: a 32 MiB slice a phase, four 8 MiB ring chunks
LATE_S = 0.3


def _events(trace_dir: str) -> list[dict]:
    (path,) = glob.glob(os.path.join(trace_dir, "tpunet-trace-rank*.json"))
    with open(path) as f:
        return [e for e in json.load(f) if e.get("ph") == "X"]


def _end(e: dict) -> float:
    return e["ts"] + e["dur"]


def _worker(rank: int, world: int, port: int, q, trace_dir: str, env: dict) -> None:
    try:
        os.environ.update(env, TPUNET_RANK=str(rank))
        os.environ.pop("TPUNET_TRACE_DIR", None)
        from tpunet import telemetry
        from tpunet.collectives import Communicator

        comm = Communicator(f"127.0.0.1:{port}", rank, world)
        x = np.full(N, float(rank + 1), np.float32)
        comm.all_reduce(x)  # untraced: wires the ring, pages the buffers
        mine = os.path.join(trace_dir, str(rank))
        with telemetry.profile(mine):
            if rank == 1:
                time.sleep(LATE_S)
            y = comm.all_reduce(x)  # coll 2: rank 1 is late
            comm.barrier()
            z = comm.all_reduce(x)  # coll 4: both start together
        assert float(y[0]) == 3.0 and float(z[-1]) == 3.0
        comm.all_reduce(x)  # tracing off again
        with telemetry.profile(os.path.join(trace_dir, f"off{rank}")):
            pass
        comm.close()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


@pytest.fixture(scope="module", params=sorted(ENGINES))
def traced(request, tmp_path_factory):
    """Both ranks' traces of one engine's late-peer run: (trace dir, rank 0's
    events, rank 1's events)."""
    d = str(tmp_path_factory.mktemp(f"parts-{request.param}"))
    run_spawn_workers(_worker, 2, extra_args=(d, ENGINES[request.param]))
    return d, _events(os.path.join(d, "0")), _events(os.path.join(d, "1"))


def _allreduces(events: list[dict]) -> list[int]:
    """coll_seq of the traced 64 MiB all-reduces, in order."""
    return sorted(e["args"]["coll_seq"] for e in events
                  if e["name"] == "allreduce" and e["args"]["nbytes"] == 4 * N)


def _parts(events: list[dict], name: str | None = None, coll: int | None = None):
    return [e for e in events if e["name"] in PARTS
            and (name is None or e["name"] == name)
            and (coll is None or e["args"]["coll"] == coll)]


def test_a_late_peer_is_wait_peer_inside_rs0(traced):
    _, ev0, _ = traced
    late, together = _allreduces(ev0)
    peer = _parts(ev0, "coll.wait_peer", late)
    assert sum(e["dur"] for e in peer) >= 0.25e6, peer
    (rs0,) = [e for e in ev0 if e["name"] == "rs.0"
              and e["args"].get("coll_seq") == late]
    big = max(peer, key=lambda e: e["dur"])
    assert rs0["ts"] <= big["ts"] and _end(big) <= _end(rs0)
    assert big["args"]["phase"] == "rs.0"
    assert sum(e["dur"] for e in _parts(ev0, "coll.wait_peer", together)) < 0.05e6


def test_parts_nest_in_their_phase_and_never_overlap(traced, request):
    engine = request.node.callspec.params["traced"]
    for events in traced[1:]:
        phases = {(e["args"]["coll_seq"], e["name"]): e for e in events
                  if "coll_seq" in e["args"]}
        parts = _parts(events)
        assert {e["name"] for e in parts} == ENGINE_PARTS[engine]
        for p in parts:
            assert not {"comm_id", "coll_seq", "seq"} & set(p["args"]), p
            assert p["args"].get("dir") in (
                ("recv", "send") if p["name"] == "coll.wait_wire" else (None,))
            ph = phases[(p["args"]["coll"], p["args"]["phase"])]
            assert ph["ts"] <= p["ts"] and _end(p) <= _end(ph), (p, ph)
            assert ph["tid"] == p["tid"]
        by_tid: dict = {}
        for p in parts:
            by_tid.setdefault(p["tid"], []).append(p)
        for same in by_tid.values():
            same.sort(key=lambda e: e["ts"])
            for a, b in zip(same, same[1:]):
                assert _end(a) <= b["ts"], (a, b)


def test_parts_cover_each_ring_step(traced):
    for events in traced[1:]:
        for coll in _allreduces(events):
            steps = [e for e in events if e["args"].get("coll_seq") == coll
                     and e["name"].split(".")[0] in ("rs", "ag")]
            assert [s["name"] for s in sorted(steps, key=lambda e: e["ts"])] == ["rs.0", "ag.0"]
            for s in steps:
                covered = sum(p["dur"] for p in _parts(events, coll=coll)
                              if p["args"]["phase"] == s["name"])
                assert covered >= 0.8 * s["dur"], (s, covered)


def test_ring_readers_read_the_same_with_parts(traced):
    sys.path.insert(0, ROOT)
    from perfbench.readers import native_ring
    from tpunet import telemetry

    d, ev0, _ = traced
    stripped = os.path.join(d, "stripped")
    for rank in ("0", "1"):
        os.makedirs(os.path.join(stripped, rank), exist_ok=True)
        (path,) = glob.glob(os.path.join(d, rank, "tpunet-trace-rank*.json"))
        with open(path) as f:
            whole = json.load(f)
        with open(os.path.join(stripped, rank, os.path.basename(path)), "w") as f:
            json.dump([e for e in whole if e.get("name") not in PARTS], f)
    lo, hi = min(e["ts"] for e in ev0) * 1e-6, max(map(_end, ev0)) * 1e-6
    assert native_ring.ring_seconds(os.path.join(d, "0"), lo, hi) == \
        native_ring.ring_seconds(os.path.join(stripped, "0"), lo, hi)

    def offsets(where: str) -> dict:
        for rank in ("0", "1"):
            for path in glob.glob(os.path.join(where, rank, "*.json")):
                os.link(path, os.path.join(where, os.path.basename(path)))
        with open(telemetry.merge_traces(where, os.path.join(where, "m.json"))) as f:
            return {(e["tid"], e["name"], e.get("args", {}).get("coll_seq")): e["ts"]
                    for e in json.load(f) if "coll_seq" in e.get("args", {})}

    assert offsets(d) == offsets(stripped)


def test_tracing_off_writes_no_part(traced):
    d = traced[0]
    for rank in ("0", "1"):
        off = os.path.join(d, f"off{rank}")
        (path,) = glob.glob(os.path.join(off, "tpunet-trace-rank*.json"))
        with open(path) as f:
            assert not [e for e in json.load(f) if e.get("name") in PARTS]
