"""Telemetry: metrics counters, TCP introspection, stage histograms, trace
spans (valid Chrome-trace JSON + cross-rank merge), scrape listener, reset."""

from __future__ import annotations

import json
import os

from conftest import free_port, run_spawn_workers


def _lint_exposition(text: str) -> None:
    """Prometheus text-format lint: every sample belongs to a family whose
    # TYPE line is adjacent to (immediately after) its # HELP line, and no
    sample appears before its family header."""
    import re

    line_re = re.compile(r"^(\w+)(?:\{[^}]*\})?\s+\S+$")
    pending_help: str | None = None
    current: str | None = None
    for line in text.splitlines():
        if line.startswith("# HELP "):
            pending_help = line.split()[2]
        elif line.startswith("# TYPE "):
            fam = line.split()[2]
            assert pending_help == fam, f"# TYPE {fam} not adjacent to its # HELP"
            current = fam
            pending_help = None
        elif line.strip():
            assert pending_help is None, f"HELP {pending_help} with no adjacent TYPE"
            m = line_re.match(line)
            assert m, f"unparseable sample line: {line!r}"
            name = m.group(1)
            base = name
            for suf in ("_bucket", "_sum", "_count"):
                if current and name == current + suf:
                    base = current
            assert base == current, f"sample {name} outside its TYPE'd family ({current})"


def _worker(rank: int, world: int, port: int, q, trace_dir: str) -> None:
    try:
        os.environ["TPUNET_TRACE_DIR"] = trace_dir
        os.environ["TPUNET_RANK"] = str(rank)
        import numpy as np

        from tpunet import telemetry
        from tpunet.collectives import Communicator

        comm = Communicator(
            coordinator=f"127.0.0.1:{port}", rank=rank, world_size=world
        )
        arr = np.ones(1 << 18, np.float32)
        out = comm.all_reduce(arr)
        assert out[0] == world

        m = telemetry.metrics()
        rank_key = (f'rank="{rank}"',)
        # A 2-rank ring AllReduce does 2(W-1)=2 sends and 2 recvs per rank.
        assert m["tpunet_isend_nbytes_count"][rank_key] >= 2
        assert m["tpunet_irecv_nbytes_count"][rank_key] >= 2
        assert m["tpunet_isend_nbytes_sum"][rank_key] >= arr.nbytes
        # Everything test()ed done: the in-flight gauge must be back to zero.
        assert m["tpunet_hold_on_request"][rank_key] == 0
        assert m["tpunet_failed_requests"][rank_key] == 0

        # TCP introspection: the sampler fires on the first chunk of each
        # stream, so per-stream gauges exist after one collective.
        for gauge in (
            "tpunet_stream_rtt_us",
            "tpunet_stream_retrans_total",
            "tpunet_stream_cwnd",
            "tpunet_stream_delivery_rate_bps",
        ):
            assert m.get(gauge), f"missing {gauge} after transfer: {sorted(m)}"
        # Fairness gauge present for both directions x all three traffic
        # classes (the QoS split: per-stream fairness reported WITHIN a
        # class), every series in (0, 1].
        fair = m["tpunet_stream_fairness_jain"]
        assert len(fair) == 6
        assert all(0.0 < v <= 1.0 for v in fair.values()), fair
        assert {telemetry.labels(k)["class"] for k in fair} == {
            "latency", "bulk", "control"}
        assert {telemetry.labels(k)["dir"] for k in fair} == {"tx", "rx"}
        # Stage-latency histograms: wire time observed for the ring messages,
        # and the numeric bucket view is monotonic with +Inf last.
        assert m["tpunet_req_wire_us_count"][rank_key] > 0
        assert m["tpunet_req_queue_us_count"][rank_key] > 0
        assert m["tpunet_req_total_us_count"][rank_key] > 0
        buckets = telemetry.histogram_buckets("tpunet_req_wire_us", m)
        assert buckets and buckets[-1][0] == float("inf")
        counts = [c for _, c in buckets]
        assert counts == sorted(counts) and counts[-1] > 0
        # The exposition is lint-clean (HELP/TYPE adjacent per family).
        _lint_exposition(telemetry.metrics_text())

        telemetry.flush_trace()
        comm.close()

        path = os.path.join(trace_dir, f"tpunet-trace-rank{rank}.json")
        assert os.path.exists(path), f"missing trace file {path}"
        # Golden: flush_trace() output is VALID Chrome-trace JSON.
        with open(path) as f:
            events = json.load(f)
        xspans = [e for e in events if e.get("ph") == "X"]
        for e in xspans:
            for field in ("name", "ts", "dur", "pid", "tid"):
                assert field in e, f"span missing {field}: {e}"
        isends = [e for e in xspans if e["name"].startswith("isend-")]
        irecvs = [e for e in xspans if e["name"].startswith("irecv-")]
        assert isends and irecvs
        assert isends[0]["args"]["nbytes"] > 0
        assert isends[0]["dur"] >= 0
        # Collective phase spans tagged with the cross-rank join key.
        colls = [e for e in xspans if "comm_id" in (e.get("args") or {})]
        assert any(e["name"] == "allreduce" for e in colls)
        assert any(e["name"].startswith("rs.") for e in colls)
        assert any(e["name"].startswith("ag.") for e in colls)
        for e in colls:
            assert "coll_seq" in e["args"]
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_metrics_and_trace(tmp_path):
    run_spawn_workers(_worker, 2, extra_args=(str(tmp_path),))
    # Cross-rank merge: both ranks' spans for the same (comm_id, coll_seq,
    # phase) land in ONE Perfetto-loadable timeline — and, with both workers
    # on one box (same host id), under ONE host track group with per-rank
    # thread tracks, instead of interleaving two top-level pid groups.
    from tpunet import telemetry

    merged_path = telemetry.merge_traces(str(tmp_path))
    with open(merged_path) as f:
        merged = json.load(f)
    by_tag: dict = {}
    host_pids: set = set()
    rank_tids: set = set()
    for ev in merged:
        args = ev.get("args") or {}
        if "comm_id" in args and "coll_seq" in args:
            assert args.get("host"), f"phase span missing host tag: {ev}"
            host_pids.add(ev["pid"])
            rank_tids.add(ev["tid"] // 1_000_000)
            by_tag.setdefault(
                (args["comm_id"], args["coll_seq"], ev["name"]), set()
            ).add(ev["tid"] // 1_000_000)
    assert by_tag, "no collective spans in merged trace"
    # Same box, same host id: one host group, both rank thread-track bands.
    assert host_pids == {1}, host_pids
    assert rank_tids == {0, 1}, rank_tids
    both = [tag for tag, tranks in by_tag.items() if tranks == {0, 1}]
    assert both, f"no tag present on both ranks: {by_tag}"
    # The per-host group metadata names the track.
    names = [e["args"]["name"] for e in merged
             if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert any(n.startswith("host ") for n in names), names
    # Alignment anchored the common tags; every event still has a timestamp.
    assert all("ts" in e for e in merged if e.get("ph") == "X")


def test_metrics_text_parses_without_activity():
    from tpunet import telemetry

    text = telemetry.metrics_text()
    assert "tpunet_isend_nbytes_count" in text
    parsed = telemetry.metrics()
    assert any(k.startswith("tpunet_") for k in parsed)
    _lint_exposition(text)


def test_bridge_minor_faults_counter_is_registered_and_only_grows():
    """tpunet_bridge_minor_faults_total{kind}: a series a bridge kind, fed by
    host_all_reduce alone (kind all_reduce), a counter in the exposition and
    in the lint's registry."""
    from pathlib import Path

    import pytest

    from tools.lint.metricsreg import check_metric_registry, registry_families
    from tpunet import _native, telemetry

    fam = "tpunet_bridge_minor_faults_total"

    def by_kind() -> dict:
        return {telemetry.labels(key)["kind"]: v
                for key, v in telemetry.metrics()[fam].items()}

    before = by_kind()
    assert set(before) == set(telemetry._BRIDGE_KINDS)
    for faults, grown in ((7, 7), (0, 7), (-3, 7), (2 ** 40, 7 + 2 ** 40)):
        telemetry.bridge_minor_faults("all_reduce", faults)
        now = by_kind()
        assert now.pop("all_reduce") == before["all_reduce"] + grown
        assert now == {k: v for k, v in before.items() if k != "all_reduce"}
    with pytest.raises(KeyError):
        telemetry.bridge_minor_faults("all_reduc", 1)
    lib = _native.load()
    assert lib.tpunet_c_bridge_minor_faults(8, 1) < 0
    assert lib.tpunet_c_bridge_minor_faults(-1, 1) < 0
    text = telemetry.metrics_text()
    assert f"# TYPE {fam} counter" in text and f'{fam}{{rank="' in text
    _lint_exposition(text)
    root = Path(__file__).resolve().parent.parent
    assert fam in registry_families(root) and check_metric_registry(root) == []
    header = (root / "cpp" / "include" / "tpunet" / "c_api.h").read_text()
    assert "tpunet_c_bridge_minor_faults(" in header


def test_metrics_parser_accepts_label_less_lines(monkeypatch):
    """Prometheus exposition allows plain `name value` lines; the old
    mandatory-`{labels}` regex silently dropped them from metrics()."""
    from tpunet import telemetry

    sample = "\n".join(
        [
            "# TYPE tpunet_faults_injected counter",
            "tpunet_faults_injected 3",
            'tpunet_stream_failovers_total{rank="0"} 2',
            "tpunet_uptime_seconds 12.5",
            "tpunet_rate 6.02e+23",
            "not a metric line at all",
            "tpunet_bad_value{rank=\"0\"} oops",
        ]
    )
    monkeypatch.setattr(telemetry, "metrics_text", lambda: sample)
    parsed = telemetry.metrics()
    assert parsed["tpunet_faults_injected"][()] == 3.0
    assert parsed["tpunet_stream_failovers_total"][('rank="0"',)] == 2.0
    assert parsed["tpunet_uptime_seconds"][()] == 12.5
    assert parsed["tpunet_rate"][()] == 6.02e23
    assert "tpunet_bad_value" not in parsed
    # The native exposition's label-less faults total parses too.
    monkeypatch.undo()
    real = telemetry.metrics()
    assert () in real["tpunet_faults_injected"]


def test_metrics_parser_preserves_label_order(monkeypatch):
    """Label tuples keep declaration order — sorting them made keys depend
    on label VALUES and scrambled le-bucket lookups."""
    from tpunet import telemetry

    sample = "\n".join(
        [
            'tpunet_demo_bucket{rank="0",le="200"} 1',
            'tpunet_demo_bucket{rank="0",le="1000"} 3',
            'tpunet_demo_bucket{rank="0",le="+Inf"} 4',
        ]
    )
    monkeypatch.setattr(telemetry, "metrics_text", lambda: sample)
    parsed = telemetry.metrics()
    assert ('rank="0"', 'le="200"') in parsed["tpunet_demo_bucket"]
    assert telemetry.labels(('rank="0"', 'le="200"')) == {"rank": "0", "le": "200"}
    buckets = telemetry.histogram_buckets("tpunet_demo", parsed)
    assert buckets == [(200.0, 1), (1000.0, 3), (float("inf"), 4)]


def _reset_worker(rank: int, world: int, port: int, q) -> None:
    """telemetry.reset() zeroes counters so warmups don't bleed into
    measurement windows (exercised over a real loopback transfer)."""
    try:
        import numpy as np

        from tpunet import telemetry
        from tpunet.transport import Net

        net = Net()
        listen = net.listen(0)
        rc_holder = {}
        import threading

        t = threading.Thread(target=lambda: rc_holder.update(rc=listen.accept()))
        t.start()
        sc = net.connect(listen.handle)
        t.join()
        rc = rc_holder["rc"]

        data = np.arange(1 << 20, dtype=np.uint8) % 251
        buf = np.zeros(1 << 20, dtype=np.uint8)
        req = rc.irecv(buf)
        sc.send(data, timeout=60)
        req.wait(timeout=60)

        m = telemetry.metrics()
        rank_key = (f'rank="{rank}"',)
        assert m["tpunet_isend_nbytes_count"][rank_key] >= 1
        assert m["tpunet_req_total_us_count"][rank_key] >= 1
        assert m.get("tpunet_stream_tx_bytes")

        telemetry.reset()
        m2 = telemetry.metrics()
        assert m2["tpunet_isend_nbytes_count"][rank_key] == 0
        assert m2["tpunet_irecv_nbytes_count"][rank_key] == 0
        assert m2["tpunet_req_total_us_count"][rank_key] == 0
        assert m2["tpunet_req_wire_us_count"][rank_key] == 0
        assert not m2.get("tpunet_stream_tx_bytes")  # zero slots are elided
        assert not m2.get("tpunet_stream_rtt_us")
        assert m2["tpunet_straggler_events_total"][rank_key] == 0

        # Counters keep working after a reset (a second transfer re-counts).
        req = rc.irecv(buf)
        sc.send(data, timeout=60)
        req.wait(timeout=60)
        m3 = telemetry.metrics()
        assert m3["tpunet_isend_nbytes_count"][rank_key] == 1

        sc.close()
        rc.close()
        listen.close()
        net.close()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_metrics_reset():
    run_spawn_workers(_reset_worker, 1)


# Families that legitimately do NOT sample zero after reset(). Every entry
# needs a reason; anything else nonzero after reset is a coverage bug the
# registry-driven test below reports by name.
_RESET_EXCEPTIONS = {
    # Jain fairness is a ratio in (0, 1]; the no-traffic value is a perfect 1.0.
    "tpunet_stream_fairness_jain": 1.0,
    # Encoded/payload wire ratio; identity (no codec engaged) reads 1.0.
    "tpunet_codec_wire_ratio": 1.0,
    # Deliberately NOT reset: it tracks live requests whose done events will
    # still arrive — zeroing mid-flight would wrap the clamp (metrics.cc).
    "tpunet_hold_on_request": None,
}


def _registry_reset_worker(rank: int, world: int, port: int, q, fams_json) -> None:
    """Registry-driven reset coverage: every family metrics.cc registers
    (parsed by tools/lint/metricsreg.py, passed in as JSON) samples zero
    after reset() — or appears in _RESET_EXCEPTIONS with a reason. A new
    family added without reset plumbing fails here by name, not by a
    dashboard going stale three PRs later."""
    try:
        import numpy as np

        from tpunet import telemetry
        from tpunet.transport import Net

        families = json.loads(fams_json)
        assert len(families) > 40, f"suspiciously small registry: {families}"

        net = Net()
        listen = net.listen(0)
        import threading

        rc_holder = {}
        t = threading.Thread(target=lambda: rc_holder.update(rc=listen.accept()))
        t.start()
        sc = net.connect(listen.handle)
        t.join()
        rc = rc_holder["rc"]
        data = np.arange(1 << 20, dtype=np.uint8) % 251
        buf = np.zeros(1 << 20, dtype=np.uint8)
        req = rc.irecv(buf)
        sc.send(data, timeout=60)
        req.wait(timeout=60)

        telemetry.reset()
        m = telemetry.metrics()
        bad = []
        for fam in families:
            if fam in _RESET_EXCEPTIONS and _RESET_EXCEPTIONS[fam] is None:
                continue
            want = _RESET_EXCEPTIONS.get(fam, 0)
            # Histogram series surface as separate top-level parser keys.
            for series in (fam, fam + "_bucket", fam + "_sum", fam + "_count"):
                for labels, value in m.get(series, {}).items():
                    if value != want:
                        bad.append(f"{series}{{{','.join(labels)}}} = {value} "
                                   f"(want {want} after reset)")
        assert not bad, "families nonzero after reset():\n  " + "\n  ".join(bad)

        sc.close()
        rc.close()
        listen.close()
        net.close()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_registry_reset_coverage():
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(repo))
    from tools.lint.metricsreg import registry_families

    fams = sorted(registry_families(repo))
    run_spawn_workers(_registry_reset_worker, 1, extra_args=(json.dumps(fams),))


def _profile_worker(rank: int, world: int, port: int, q, trace_dir: str) -> None:
    """profile() enables tracing at RUNTIME (no TPUNET_TRACE_DIR at load)."""
    try:
        os.environ.pop("TPUNET_TRACE_DIR", None)
        import numpy as np

        from tpunet import telemetry
        from tpunet.transport import Net

        net = Net()
        listen = net.listen(0)
        import threading

        rc_holder = {}
        t = threading.Thread(target=lambda: rc_holder.update(rc=listen.accept()))
        t.start()
        sc = net.connect(listen.handle)
        t.join()
        rc = rc_holder["rc"]

        with telemetry.profile(trace_dir) as prof:
            data = np.arange(1 << 18, dtype=np.uint8) % 251
            buf = np.zeros(1 << 18, dtype=np.uint8)
            req = rc.irecv(buf)
            sc.send(data, timeout=60)
            req.wait(timeout=60)
        files = prof.rank_files()
        assert files, f"profile() wrote no trace files in {trace_dir}"
        with open(files[0]) as f:
            events = json.load(f)  # valid JSON after the context exits
        assert any(e.get("name", "").startswith("isend-") for e in events)

        # Tracing is OFF again after the context: a post-profile transfer
        # must not grow the trace file.
        size_before = os.path.getsize(files[0])
        req = rc.irecv(buf)
        sc.send(data, timeout=60)
        req.wait(timeout=60)
        telemetry.flush_trace()
        assert os.path.getsize(files[0]) == size_before

        sc.close()
        rc.close()
        listen.close()
        net.close()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_profile_context_manager(tmp_path):
    run_spawn_workers(_profile_worker, 1, extra_args=(str(tmp_path),))


def _scrape_worker(rank: int, world: int, port: int, q, scrape_port: str) -> None:
    """The on-demand /metrics listener serves a lint-clean exposition."""
    try:
        os.environ["TPUNET_METRICS_PORT"] = scrape_port
        os.environ["TPUNET_RANK"] = str(rank)
        import time

        from tpunet import telemetry

        telemetry.metrics_text()  # constructs the singleton -> starts listener
        deadline = time.monotonic() + 10
        text = None
        while time.monotonic() < deadline:
            try:
                text = telemetry.scrape(int(scrape_port))
                break
            except OSError:
                time.sleep(0.1)
        assert text is not None, "scrape listener never came up"
        assert "tpunet_isend_nbytes_count" in text
        assert "# HELP tpunet_isend_nbytes" in text
        _lint_exposition(text)

        # Framing: Prometheus scrapers key on the versioned Content-Type and
        # an exact Content-Length (the listener closes after one response).
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{scrape_port}/metrics", timeout=5) as r:
            body = r.read()
            assert r.headers["Content-Type"] == "text/plain; version=0.0.4"
            assert int(r.headers["Content-Length"]) == len(body)
        # Liveness endpoint: /healthz answers 200 "ok" without rendering the
        # full exposition — what a k8s probe polls at 1 Hz.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{scrape_port}/healthz", timeout=5) as r:
            body = r.read()
            assert r.status == 200
            assert body == b"ok\n"
            assert r.headers["Content-Type"] == "text/plain"
            assert int(r.headers["Content-Length"]) == len(body)
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_metrics_scrape_listener():
    run_spawn_workers(_scrape_worker, 1, extra_args=(str(free_port()),))


def _push_worker(rank: int, world: int, port: int, q) -> None:
    """Point the native pushgateway client at an in-process HTTP sink and
    check one push arrives (reference: Prometheus push thread with basic
    auth, nthread:183-211)."""
    try:
        import socket
        import threading

        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(4)
        sink_port = srv.getsockname()[1]
        received: list[bytes] = []
        got_one = threading.Event()

        def serve():
            while not got_one.is_set():
                try:
                    conn, _ = srv.accept()
                except OSError:
                    return
                data = b""
                conn.settimeout(2)
                try:
                    while b"\r\n\r\n" not in data or len(data) < 200:
                        chunk = conn.recv(65536)
                        if not chunk:
                            break
                        data += chunk
                except OSError:
                    pass
                conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: 0\r\n\r\n")
                conn.close()
                received.append(data)
                if b"tpunet_" in data:
                    got_one.set()

        t = threading.Thread(target=serve, daemon=True)
        t.start()

        os.environ["TPUNET_METRICS_ADDR"] = f"user:pw@127.0.0.1:{sink_port}"
        os.environ["TPUNET_METRICS_INTERVAL_MS"] = "50"
        os.environ["TPUNET_RANK"] = str(rank)
        from tpunet import telemetry

        telemetry.metrics_text()  # constructs the singleton -> starts pusher
        assert got_one.wait(timeout=15), "no metrics push arrived"
        payload = b"".join(received)
        assert b"PUT /metrics/job/tpunet/rank/0" in payload
        assert b"Authorization: Basic " in payload
        assert b"tpunet_isend_nbytes_count" in payload
        srv.close()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_metrics_push():
    run_spawn_workers(_push_worker, 1)


def _ephemeral_port_worker(rank: int, world: int, port: int, q) -> None:
    """TPUNET_METRICS_PORT=0 binds an EPHEMERAL port: the env still reads
    0, the bound port is learnable only via telemetry.metrics_port(), and
    scrape() with no argument finds it — the multi-tier-on-one-box
    contract (serving tiers each run their own listener with zero port
    bookkeeping)."""
    try:
        os.environ["TPUNET_METRICS_PORT"] = "0"
        os.environ["TPUNET_RANK"] = str(rank)

        from tpunet import telemetry

        telemetry.metrics_text()  # constructs the singleton -> binds
        bound = telemetry.metrics_port()
        assert bound > 0, "ephemeral bind did not happen"
        assert os.environ["TPUNET_METRICS_PORT"] == "0"  # env untouched
        text = telemetry.scrape()  # no port arg: native fallback
        assert "tpunet_serve_queue_depth" in text
        assert "tpunet_req_ttft_us_count" in text
        _lint_exposition(text)
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        q.put((rank, f"FAIL: {type(e).__name__}: {e}"))


def test_metrics_port_ephemeral_bind():
    run_spawn_workers(_ephemeral_port_worker, 1)


def test_serve_observe_validation():
    """The serving-tier SLO accessors reject unknown kinds/tiers loudly."""
    import pytest

    from tpunet import telemetry

    with pytest.raises(ValueError, match="kind"):
        telemetry.serve_observe("latency", 1)
    with pytest.raises(ValueError, match="tier"):
        telemetry.serve_queue_depth("edge", 1)
