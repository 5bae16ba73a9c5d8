"""Program spans (tpunet.telemetry.span): the DCN bridge's callback and
fit()'s loop in the native trace file and on the JAX profiler's timeline,
the bridge's counters, and what all of it costs when nothing listens."""

from __future__ import annotations

import glob
import json
import os
import statistics
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

# Module level so mp-spawn children (which re-import this module, but not
# conftest.py) are held to the CPU too.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from conftest import free_port, run_spawn_workers  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BRIDGE_CHILDREN = ["dcn.bridge.stage_in", "dcn.bridge.collective", "dcn.bridge.stage_out"]


def _native_events(trace_dir: str) -> list[dict]:
    (path,) = glob.glob(os.path.join(trace_dir, "tpunet-trace-rank*.json"))
    with open(path) as f:
        return [e for e in json.load(f) if e.get("ph") == "X"]


def _within(child: dict, parent: dict) -> bool:
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def _profiler_spans(trace_dir: str) -> list[tuple[str, dict, float, float]]:
    """(name, stats, start_s, duration_s) of the "tpunet:" events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpunet:"):
                    out.append((ev.name[len("tpunet:"):], dict(ev.stats),
                                ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


@pytest.fixture()
def callback_bridge(monkeypatch):
    """World of one, collectives through io_callback (the path every
    non-CPU backend takes)."""
    from tpunet import distributed

    monkeypatch.setenv("TPUNET_FFI_COLLECTIVES", "0")
    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    jax.clear_caches()  # the flag is read at trace time
    yield
    jax.clear_caches()
    distributed.finalize()


def test_bridge_spans_in_the_native_file(callback_bridge, tmp_path):
    import jax.numpy as jnp

    from tpunet import telemetry
    from tpunet.interop import dcn_all_gather, dcn_psum

    x = jnp.arange(4096, dtype=jnp.float32)
    with telemetry.profile(str(tmp_path)):
        jax.block_until_ready(jax.jit(dcn_psum)(x))
        jax.block_until_ready(jax.jit(dcn_all_gather)(x))
    events = _native_events(str(tmp_path))
    bridges = [e for e in events if e["name"] == "dcn.bridge"]
    assert [b["args"]["kind"] for b in bridges] == ["all_reduce", "all_gather"]
    assert bridges[0]["args"]["seq"] != bridges[1]["args"]["seq"]
    for b in bridges:
        assert b["args"]["nbytes"] == x.nbytes and "parent" not in b["args"]
        kids = [e for e in events if e["args"].get("parent") == "dcn.bridge"
                and e["args"]["seq"] == b["args"]["seq"]]
        assert [k["name"] for k in sorted(kids, key=lambda e: e["ts"])] == BRIDGE_CHILDREN
        assert all(_within(k, b) and k["tid"] == b["tid"] for k in kids)
    # what marks a collective phase for merge_traces() and the ring's
    # readers is on no program span
    for e in events:
        if e["name"].startswith(("dcn.", "train.")):
            assert not {"coll_seq", "comm_id"} & set(e["args"])
    assert telemetry.merge_traces(str(tmp_path))  # still merges


def _two_rank_worker(rank: int, world: int, port: int, q, trace_dir: str) -> None:
    try:
        os.environ["TPUNET_RANK"] = str(rank)
        os.environ["TPUNET_FFI_COLLECTIVES"] = "0"
        os.environ.pop("TPUNET_TRACE_DIR", None)
        import jax.numpy as jnp

        from tpunet import distributed, telemetry
        from tpunet.interop import dcn_psum

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        x = jnp.full((1 << 18,), float(rank + 1), jnp.float32)
        mine = os.path.join(trace_dir, str(rank))
        with telemetry.profile(mine):
            y = jax.block_until_ready(jax.jit(dcn_psum)(x))
        assert float(y[0]) == 3.0
        events = _native_events(mine)
        (coll,) = [e for e in events if e["name"] == "dcn.bridge.collective"]
        phases = [e for e in events if "coll_seq" in e["args"]]
        names = {e["name"] for e in phases}
        assert "allreduce" in names and any(n.startswith("rs.") for n in names), names
        assert all(_within(p, coll) for p in phases), (coll, phases)
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_native_ring_phases_fall_inside_the_collective_span(tmp_path):
    run_spawn_workers(_two_rank_worker, 2, extra_args=(str(tmp_path),))


def test_mirrored_pairs_share_a_clock(callback_bridge, tmp_path):
    """Both sinks on: every root span is once in the native file and once in
    the .xplane.pb, and the two clocks differ by one offset."""
    import jax.numpy as jnp

    from tpunet import telemetry
    from tpunet.interop import dcn_psum

    f = jax.jit(dcn_psum)
    x = jnp.arange(1 << 16, dtype=jnp.float32)
    jax.block_until_ready(f(x))
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.profile(str(tmp_path / "native")):
            for _ in range(6):
                jax.block_until_ready(f(x))
                time.sleep(0.01)
    seen = _profiler_spans(str(tmp_path))
    assert {n for n, _, _, _ in seen} == {"dcn.bridge", *BRIDGE_CHILDREN}
    roots = {(n, st["seq"]): s for n, st, s, _ in seen if "seq" in st}
    native = {(e["name"], e["args"]["seq"]): e["ts"] * 1e-6
              for e in _native_events(str(tmp_path / "native"))
              if "parent" not in e["args"]}
    assert len(roots) == 6 and set(roots) == set(native)
    offsets = [roots[k] - native[k] for k in roots]
    mid = statistics.median(offsets)
    assert max(abs(o - mid) for o in offsets) < 1e-3, offsets
    # durations agree too: one span, two records
    for n, st, _, d in seen:
        if n == "dcn.bridge":
            (e,) = [e for e in _native_events(str(tmp_path / "native"))
                    if e["name"] == n and e["args"]["seq"] == st["seq"]]
            assert abs(e["dur"] * 1e-6 - d) < 1e-3


def test_span_off_makes_no_native_call_and_costs_microseconds(monkeypatch):
    from tpunet import _native, telemetry

    lib = _native.load()
    calls = []
    real = lib.tpunet_c_trace_span
    monkeypatch.setattr(telemetry, "_native_spans", False)
    monkeypatch.setattr(lib, "tpunet_c_trace_span",
                        lambda *a: calls.append(a) or real(*a))
    n = 100_000
    t = time.perf_counter()
    for _ in range(n):
        with telemetry.span("train.feed"):
            pass
    each = (time.perf_counter() - t) / n
    assert not calls
    # the budget is 20 us a STEP (a handful of spans); a loaded box still
    # stays far under that for one
    assert each < 20e-6, each
    assert getattr(telemetry._span_local, "top", None) is None
    # on, but the tracer itself off: one call, its answer 0, then none
    monkeypatch.setattr(telemetry, "_native_spans", True)
    for _ in range(3):
        with telemetry.span("train.feed"):
            pass
    assert len(calls) == 1 and telemetry._native_spans is False


def test_telemetry_imports_no_jax():
    code = (
        "import sys\n"
        "from tpunet import telemetry\n"
        "with telemetry.span('dcn.bridge', kind='all_reduce', nbytes=4):\n"
        "    with telemetry.span('dcn.bridge.collective'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'telemetry pulled jax in'\n"
        "assert 'tpunet._native' in sys.modules\n"
        "from tpunet import _native\n"
        "assert _native._lib is None, 'an idle span loaded the library'\n")
    env = {k: v for k, v in os.environ.items() if k != "TPUNET_TRACE_DIR"}
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _bridge_counts() -> dict:
    from tpunet import telemetry

    m = telemetry.metrics()
    out = {}
    for fam in ("tpunet_bridge_calls_total", "tpunet_bridge_bytes_total"):
        for key, v in m[fam].items():
            if v:
                out[(fam, telemetry.labels(key)["kind"])] = v
    return out


def test_bridge_counters_count_callbacks_and_operand_bytes(callback_bridge):
    import jax.numpy as jnp

    from tpunet import telemetry
    from tpunet.interop import (dcn_all_reduce_finish, dcn_all_reduce_start,
                                dcn_broadcast, dcn_psum)

    x = jnp.ones((1000,), jnp.float32)
    telemetry.reset()
    assert _bridge_counts() == {}
    f = jax.jit(dcn_psum)
    for _ in range(3):
        jax.block_until_ready(f(x))
    jax.block_until_ready(jax.jit(dcn_broadcast)(x.astype(jnp.bfloat16)))
    jax.block_until_ready(jax.jit(
        lambda v: dcn_all_reduce_finish(dcn_all_reduce_start(v), v))(x))
    assert _bridge_counts() == {
        ("tpunet_bridge_calls_total", "all_reduce"): 3,
        ("tpunet_bridge_bytes_total", "all_reduce"): 12000,
        ("tpunet_bridge_calls_total", "broadcast"): 1,
        ("tpunet_bridge_bytes_total", "broadcast"): 2000,
        ("tpunet_bridge_calls_total", "all_reduce_start"): 1,
        ("tpunet_bridge_bytes_total", "all_reduce_start"): 4000,
        ("tpunet_bridge_calls_total", "all_reduce_finish"): 1,
        ("tpunet_bridge_bytes_total", "all_reduce_finish"): 4,  # the ticket
    }
    with pytest.raises(ValueError):
        telemetry.bridge_call("psum", 1)


def test_ffi_path_counts_no_bridge_call(monkeypatch):
    import jax.numpy as jnp

    from tpunet import distributed, interop, telemetry

    monkeypatch.delenv("TPUNET_FFI_COLLECTIVES", raising=False)
    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    jax.clear_caches()
    try:
        if not interop._ffi_available():
            pytest.skip("libtpunet.so was built without the FFI handlers")
        telemetry.reset()
        x = jnp.ones((1000,), jnp.float32)
        assert "tpunet_all_reduce" in jax.jit(interop.dcn_psum).lower(x).as_text()
        jax.block_until_ready(jax.jit(interop.dcn_psum)(x))
        assert _bridge_counts() == {}
    finally:
        jax.clear_caches()
        distributed.finalize()


def test_fit_emits_one_train_step_a_step(tmp_path):
    import jax.numpy as jnp

    from tpunet import telemetry
    from tpunet.train import TrainState, fit

    def step(state, inputs, labels, rng):
        return TrainState(state.params, state.opt_state, state.step + 1), jnp.sum(inputs)

    state = TrainState({"w": jnp.zeros(())}, (), jnp.zeros((), jnp.int32))
    batches = [(jnp.ones((2,)), jnp.zeros((2,)))] * 4
    logged = []
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.profile(str(tmp_path / "native")):
            out = fit(state, step, iter(batches), steps=4, log_every=2,
                      log_fn=logged.append, eval_every=2,
                      eval_fn=lambda s: {"step": int(s.step)})
    assert int(out.step) == 4 and len(logged) == 4  # 2 losses, 2 evals
    events = _native_events(str(tmp_path / "native"))
    steps = sorted((e for e in events if e["name"] == "train.step"),
                   key=lambda e: e["ts"])
    assert [s["args"]["step"] for s in steps] == [0, 1, 2, 3]
    assert len({s["args"]["seq"] for s in steps}) == 4

    def kids(s):
        return sorted((e for e in events if e["args"].get("parent") == "train.step"
                       and e["args"]["seq"] == s["args"]["seq"]), key=lambda e: e["ts"])

    assert [k["name"] for k in kids(steps[0])] == ["train.feed", "train.step_fn"]
    assert [k["name"] for k in kids(steps[1])] == [
        "train.feed", "train.step_fn", "train.loss_fetch", "train.eval"]
    assert all(_within(k, s) for s in steps for k in kids(s))
    # the final evaluation runs after the loop, outside any step
    assert [e["name"] for e in events if "parent" not in e["args"]
            and e["name"] != "train.step"] == ["train.eval"]
    # the profiler's step view groups by step_num
    seen = _profiler_spans(str(tmp_path))
    assert sorted(st["step_num"] for n, st, _, _ in seen if n == "train.step") == [0, 1, 2, 3]
    assert sum(n == "train.step_fn" for n, _, _, _ in seen) == 4


def test_span_survives_an_exception_and_threads_keep_their_own_roots(tmp_path):
    from tpunet import telemetry

    def other():
        with telemetry.span("dcn.bridge", kind="all_reduce", nbytes=8):
            pass

    with telemetry.profile(str(tmp_path)):
        with pytest.raises(KeyError):
            with telemetry.span("train.step", step_num=7):
                with telemetry.span("train.step_fn"):
                    t = threading.Thread(target=other)
                    t.start()
                    t.join()
                    raise KeyError("boom")
        assert getattr(telemetry._span_local, "top", None) is None
    by = {e["name"]: e for e in _native_events(str(tmp_path))}
    assert by["train.step_fn"]["args"]["parent"] == "train.step"
    assert by["train.step_fn"]["args"]["seq"] == by["train.step"]["args"]["seq"]
    # the other thread's span is a root of its own, on its own track
    assert "parent" not in by["dcn.bridge"]["args"]
    assert by["dcn.bridge"]["args"]["seq"] != by["train.step"]["args"]["seq"]
    assert by["dcn.bridge"]["tid"] != by["train.step"]["tid"]


def test_trace_span_entry_validates_and_says_when_it_is_off(tmp_path):
    from tpunet import _native, telemetry

    lib = _native.load()
    args = (10, 5, 1, 0, None, None, -1, -1)
    assert lib.tpunet_c_trace_span(b"dcn.bridge", *args) == 0  # tracing off
    for bad in (b"", b'a"b', b"a b", b"x" * 65):
        assert lib.tpunet_c_trace_span(bad, *args) < 0
    assert lib.tpunet_c_trace_span(b"ok", 10, 5, 1, 0, b'p"', None, -1, -1) < 0
    assert lib.tpunet_c_bridge_call(8, 1) < 0 and lib.tpunet_c_bridge_call(-1, 1) < 0
    assert lib.tpunet_c_bridge_chunks(8, 1, 1) < 0 and lib.tpunet_c_bridge_chunks(-1, 1, 1) < 0
    with telemetry.profile(str(tmp_path)):
        assert lib.tpunet_c_trace_span(b"dcn.bridge", *args) == 1
        assert lib.tpunet_c_trace_span(b"a.b:c-d_E9", 10, 5, 1, 0, b"dcn.bridge",
                                       b"all_reduce", 3, 2) == 1
    first, last = _native_events(str(tmp_path))[-2:]
    assert first["args"] == {"seq": 1, "nbytes": 0}  # no step, no chunk
    assert last["args"] == {"seq": 1, "nbytes": 0, "parent": "dcn.bridge",
                            "kind": "all_reduce", "step": 3, "chunk": 2}


def test_lints_know_the_new_entries():
    from pathlib import Path

    from tools.lint.cabi import check_c_abi
    from tools.lint.metricsreg import check_metric_registry, registry_families

    root = Path(ROOT)
    assert check_c_abi(root) == [] and check_metric_registry(root) == []
    assert {"tpunet_bridge_calls_total", "tpunet_bridge_bytes_total",
            "tpunet_bridge_chunks_total",
            "tpunet_bridge_chunks_in_flight_max"} <= registry_families(root)
    header = (root / "cpp" / "include" / "tpunet" / "c_api.h").read_text()
    assert all(f"{name}(" in header for name in (
        "tpunet_c_trace_span", "tpunet_c_bridge_call", "tpunet_c_bridge_chunks"))
