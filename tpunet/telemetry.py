"""Transport observability accessors.

The native layer records per-request metrics (always-on counters), deep
per-stream TCP introspection (rate-limited ``getsockopt(TCP_INFO)`` gauges,
Jain's fairness index, straggler events), request stage-latency histograms
(queueing delay separable from wire time), and — when tracing is on —
Chrome-trace spans for every request plus collective phase spans tagged
``(comm_id, coll_seq, phase)``. This module reads it all from Python:

  metrics_text()      -> Prometheus exposition text (lint-clean HELP/TYPE)
  metrics()           -> parsed {metric_name: {labels_tuple: value}}
  labels(key)         -> a metrics() label tuple as an ordered dict
  histogram_buckets() -> [(upper_bound, cumulative_count)] with `le` parsed
                         numerically (+Inf last)
  reset()             -> zero every counter so warmups don't bleed into
                         measurement windows
  flush_trace()       -> write buffered spans (file is valid JSON after)
  profile()           -> context manager that enables tracing at runtime
  span()              -> context manager around a piece of the PROGRAM's own
                         host work (the DCN bridge's callback, fit()'s loop):
                         one span in the native trace file and one on the
                         JAX profiler's timeline
  merge_traces()      -> join per-rank trace files into one Perfetto
                         timeline, aligned by collective tags
  scrape()            -> GET the native /metrics listener
  metrics_port()      -> bound port of the /metrics listener (0 = none);
                         the only way to learn an ephemeral-port bind
  serve_observe()     -> record one serving-tier TTFT/TPOT latency sample
  serve_queue_depth() -> set a serving tier's queue-depth gauge
  rewire_observe()    -> record one elastic rewire-phase duration sample
  churn_event()       -> count one membership-churn event by kind
  world_size()        -> set the live world-size gauge
  swap_observe()      -> record one weight-swap phase duration sample
  swap_event()        -> count one weight-swap event by kind
  weight_version()    -> set the serving checkpoint-version gauge
  bridge_call()       -> count one DCN-bridge host callback and its bytes
  bridge_chunks()     -> count one boundary exchange's chunks and their depth
  bridge_minor_faults() -> count the page faults one boundary exchange paid
  flightrec_dump()    -> write this rank's flight-recorder ring to disk
  flightrec_stats()   -> (events_recorded, ring_capacity) of the recorder

Env flags (rank-gated 0-7 like the reference, nthread:108-130):
  TPUNET_TRACE_DIR            directory for Chrome-trace JSON (Perfetto)
  TPUNET_METRICS_ADDR         pushgateway "user:pass@host:port"
  TPUNET_METRICS_INTERVAL_MS  push period, default 1000
  TPUNET_METRICS_PORT         on-demand /metrics scrape listener port
                              (unset = off; 0 = bind an EPHEMERAL port,
                              readable via metrics_port())
  TPUNET_TCPINFO_INTERVAL_MS  TCP_INFO sample period per stream (0 = off)
  TPUNET_STRAGGLER_FACTOR     straggler threshold k over the median sRTT
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import itertools
import json
import os
import re
import sys
import threading
import time
import urllib.request

from tpunet import _native


def metrics_text() -> str:
    lib = _native.load()
    # Counters move concurrently, so the text can grow between the sizing
    # call and the copy; retry until the copy fits its own length.
    cap = 16384
    while True:
        buf = ctypes.create_string_buffer(cap)
        n = lib.tpunet_c_metrics_text(buf, cap)
        if n < 0:
            raise _native.NativeError(n, "metrics_text")
        if n < cap:
            return buf.value.decode()
        cap = n + 256


# Prometheus exposition line: the `{labels}` block is OPTIONAL — plain
# `name value` lines are valid exposition and the old mandatory-braces
# pattern silently dropped them from metrics().
_LINE = re.compile(r"^(\w+)(?:\{([^}]*)\})?\s+([0-9.eE+-]+|[+-]?Inf|NaN)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def metrics() -> dict:
    """Parse the Prometheus text into {name: {(label="v", ...): float}}.

    Label tuples preserve the exposition's declaration order (sorting them
    scrambled `le` bucket bounds and made keys depend on label VALUES).
    Lines without a label block parse to the empty label tuple ()."""
    out: dict = {}
    for line in metrics_text().splitlines():
        if line.startswith("#"):
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        key = tuple(labels.split(",")) if labels else ()
        out.setdefault(name, {})[key] = float(value)
    return out


def labels(key: tuple) -> dict:
    """A metrics() label tuple as an insertion-ordered {name: value} dict:
    labels(('rank="0"', 'le="1024"')) -> {"rank": "0", "le": "1024"}."""
    out = {}
    for part in key:
        m = _LABEL.match(part)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def histogram_buckets(name: str, parsed: dict | None = None) -> list[tuple[float, int]]:
    """Numeric view of a histogram family: [(upper_bound, cumulative_count)]
    sorted by bound with +Inf last, so buckets can be consumed numerically.
    `name` is the family name without the `_bucket` suffix; counts with the
    same `le` across other label sets (e.g. several ranks) are summed."""
    if parsed is None:
        parsed = metrics()
    by_bound: dict[float, int] = {}
    for key, value in parsed.get(name + "_bucket", {}).items():
        le = labels(key).get("le")
        if le is None:
            continue
        bound = float("inf") if le in ("+Inf", "Inf") else float(le)
        by_bound[bound] = by_bound.get(bound, 0) + int(value)
    return sorted(by_bound.items())


def reset() -> None:
    """Zero every metric counter/histogram/gauge (trace spans and the
    in-flight gauge are untouched) — call between a warmup and a measurement
    window so the first doesn't bleed into the second."""
    lib = _native.load()
    _native.check(lib.tpunet_c_metrics_reset(), "metrics_reset")


def metrics_port() -> int:
    """Bound port of the on-demand /metrics listener, or 0 when none is up.

    With ``TPUNET_METRICS_PORT=0`` the native layer binds an EPHEMERAL port
    (so several tiers on one loopback box can each run a listener without
    port bookkeeping) and this accessor is the only way to learn which —
    the env var still reads 0. Forces singleton construction, so it is safe
    to call before any engine exists."""
    lib = _native.load()
    return int(lib.tpunet_c_metrics_port())


_SERVE_KINDS = {"ttft": 0, "tpot": 1}
_SERVE_TIERS = {"router": 0, "prefill": 1, "decode": 2}


def serve_observe(kind: str, us: int) -> None:
    """Record one serving-tier latency sample (microseconds) into the
    ``tpunet_req_ttft_us`` (kind="ttft") or ``tpunet_req_tpot_us``
    (kind="tpot") histogram — the per-request SLO families the
    disaggregated serving tier feeds (docs/DESIGN.md "Serving tier")."""
    if kind not in _SERVE_KINDS:
        raise ValueError(f"kind must be one of {sorted(_SERVE_KINDS)}, got {kind!r}")
    lib = _native.load()
    _native.check(
        lib.tpunet_c_serve_observe(_SERVE_KINDS[kind], max(0, int(us))),
        "serve_observe",
    )


def serve_queue_depth(tier: str, depth: int) -> None:
    """Set the instantaneous ``tpunet_serve_queue_depth{tier=...}`` gauge
    for one serving tier ("router", "prefill" or "decode")."""
    if tier not in _SERVE_TIERS:
        raise ValueError(f"tier must be one of {sorted(_SERVE_TIERS)}, got {tier!r}")
    lib = _native.load()
    _native.check(
        lib.tpunet_c_serve_queue_depth(_SERVE_TIERS[tier], max(0, int(depth))),
        "serve_queue_depth",
    )


_REWIRE_PHASES = {"detect": 0, "quiesce": 1, "rendezvous": 2, "rewire": 3}
_CHURN_KINDS = {"kill": 0, "join": 1, "shrink": 2, "grow": 3, "readmit": 4}


def rewire_observe(phase: str, us: int) -> None:
    """Record one elastic rewire-phase duration sample (microseconds) into
    ``tpunet_rewire_duration_us{phase=...}`` — the bounded-recovery
    histograms the churn suite gates on (docs/DESIGN.md "Elastic churn").
    Phases: "detect" (last good collective -> failure classified / join
    agreed), "quiesce" (old comm finalized), "rendezvous" (membership
    sealed), "rewire" (new communicator wired)."""
    if phase not in _REWIRE_PHASES:
        raise ValueError(
            f"phase must be one of {sorted(_REWIRE_PHASES)}, got {phase!r}")
    lib = _native.load()
    _native.check(
        lib.tpunet_c_rewire_observe(_REWIRE_PHASES[phase], max(0, int(us))),
        "rewire_observe",
    )


def churn_event(kind: str) -> None:
    """Count one membership-churn event into
    ``tpunet_churn_events_total{kind=...}`` ("kill", "join", "shrink",
    "grow" or "readmit")."""
    if kind not in _CHURN_KINDS:
        raise ValueError(
            f"kind must be one of {sorted(_CHURN_KINDS)}, got {kind!r}")
    lib = _native.load()
    _native.check(lib.tpunet_c_churn_event(_CHURN_KINDS[kind]), "churn_event")


def world_size(world: int) -> None:
    """Set the ``tpunet_world_size`` gauge — the live communicator's world
    as this rank last saw it (the churn suite's "world came back" gate)."""
    lib = _native.load()
    _native.check(lib.tpunet_c_world_size(max(0, int(world))), "world_size")


_SWAP_PHASES = {"announce": 0, "broadcast": 1, "verify": 2, "flip": 3}
_SWAP_KINDS = {"publish": 0, "commit": 1, "abort": 2, "retry": 3,
               "mismatch": 4}


def swap_observe(phase: str, us: int) -> None:
    """Record one live weight-swap phase duration sample (microseconds)
    into ``tpunet_weight_swap_duration_us{phase=...}`` — the publication
    pipeline's stage histograms (docs/DESIGN.md "Live weight updates").
    Phases: "announce" (SWAP_BEGIN frames out / receiver armed),
    "broadcast" (chunked bf16 tree broadcast on the bulk class), "verify"
    (cross-rank CRC32C digest agreement), "flip" (new server built,
    version live)."""
    if phase not in _SWAP_PHASES:
        raise ValueError(
            f"phase must be one of {sorted(_SWAP_PHASES)}, got {phase!r}")
    lib = _native.load()
    _native.check(
        lib.tpunet_c_swap_observe(_SWAP_PHASES[phase], max(0, int(us))),
        "swap_observe",
    )


def swap_event(kind: str) -> None:
    """Count one weight-swap event into
    ``tpunet_swap_events_total{kind=...}`` ("publish", "commit", "abort",
    "retry" or "mismatch")."""
    if kind not in _SWAP_KINDS:
        raise ValueError(
            f"kind must be one of {sorted(_SWAP_KINDS)}, got {kind!r}")
    lib = _native.load()
    _native.check(lib.tpunet_c_swap_event(_SWAP_KINDS[kind]), "swap_event")


def weight_version(version: int) -> None:
    """Set the ``tpunet_weight_version`` gauge — the checkpoint version
    this rank is serving (the swap lane's "v2 reached every rank" gate)."""
    lib = _native.load()
    _native.check(
        lib.tpunet_c_weight_version(max(0, int(version))), "weight_version")


def flush_trace() -> None:
    lib = _native.load()
    _native.check(lib.tpunet_c_trace_flush(), "trace_flush")


def flightrec_dump(dir: str | None = None, reason: str = "api") -> str:
    """Write this rank's flight-recorder ring (docs/DESIGN.md §6c) to
    ``<dir>/tpunet-flightrec-rank<R>.json`` and return the path. ``dir=None``
    uses the directory resolved when the recorder initialized
    (TPUNET_TRACE_DIR when set, else "."). ``reason`` lands in the dump
    header so a postmortem can tell an on-demand snapshot from a watchdog
    verdict. Raises NativeError when the recorder is disabled
    (TPUNET_FLIGHTREC_EVENTS=0) or the target is unwritable."""
    lib = _native.load()
    buf = ctypes.create_string_buffer(1024)
    n = lib.tpunet_c_flightrec_dump(
        dir.encode() if dir else None, reason.encode(), buf, len(buf))
    if n < 0:
        _native.check(n, "flightrec_dump")
    return buf.value.decode()


def flightrec_dump_verdict(reason: str) -> str | None:
    """Best-effort flight-recorder dump for Python-side terminal verdicts
    (rewire / weight-swap deadline raise sites — the native layer dumps its
    own watchdog/CRC verdicts). Never raises: the typed error being raised
    is the story, a failed dump must not replace it. Returns the dump path,
    or None when the recorder is disabled or the dump failed."""
    try:
        return flightrec_dump(reason=reason)
    except Exception:
        return None


def flightrec_stats() -> tuple[int, int]:
    """(events_ever_recorded, ring_capacity) of the flight recorder. The
    first is the monotonic claim cursor (NOT clamped to capacity — subtract
    to learn how many events the ring has dropped); both are 0 when the
    recorder is disabled or has never recorded."""
    lib = _native.load()
    rec = ctypes.c_uint64()
    cap = ctypes.c_uint64()
    _native.check(
        lib.tpunet_c_flightrec_stats(ctypes.byref(rec), ctypes.byref(cap)),
        "flightrec_stats")
    return int(rec.value), int(cap.value)


class _Profile:
    """Handle yielded by profile(): where the trace files land."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.merged_path: str | None = None

    def rank_files(self) -> list[str]:
        return sorted(glob.glob(os.path.join(self.trace_dir, "tpunet-trace-rank*.json")))


@contextlib.contextmanager
def profile(trace_dir: str | None = None, merge: bool = False):
    """Enable tracing at runtime for the duration of the block.

    Unlike TPUNET_TRACE_DIR (read once at library load), this retargets the
    native tracer on entry and flushes + disables on exit, so a profile can
    bracket exactly one measurement window::

        with telemetry.profile("/tmp/traces") as prof:
            comm.all_reduce(x)
        telemetry.merge_traces(prof.trace_dir)

    With merge=True the per-rank files present in trace_dir are merged into
    one Perfetto timeline on exit (single-host convenience; multi-host jobs
    collect the rank files first and call merge_traces() themselves)."""
    lib = _native.load()
    trace_dir = trace_dir or os.environ.get("TPUNET_TRACE_DIR") or "/tmp/tpunet-traces"
    os.makedirs(trace_dir, exist_ok=True)
    global _native_spans
    _native.check(lib.tpunet_c_trace_set_dir(trace_dir.encode()), "trace_set_dir")
    _native_spans = True
    prof = _Profile(trace_dir)
    try:
        yield prof
    finally:
        _native_spans = False
        _native.check(lib.tpunet_c_trace_flush(), "trace_flush")
        _native.check(lib.tpunet_c_trace_set_dir(b""), "trace_set_dir")
        if merge:
            prof.merged_path = merge_traces(trace_dir)


# -- program spans ------------------------------------------------------------

# Whether span() feeds the native tracer: TPUNET_TRACE_DIR at load (the
# native layer reads it once, then), or an open profile(). A rank the native
# gate (ranks 0-7) leaves out learns so from its first span's answer.
_native_spans = bool(os.environ.get("TPUNET_TRACE_DIR")
                     or os.environ.get("BAGUA_NET_JAEGER_ADDRESS"))
_span_local = threading.local()  # .top: the innermost open span of the thread
_span_seq = itertools.count(1)   # one number a ROOT span, per process


class span:
    """A span around a piece of the program's own host work.

    ``with telemetry.span("dcn.bridge", kind="all_reduce", nbytes=n): ...``

    Two sinks, one name (docs/DESIGN.md 6c "Trace span model"):

    - the native tracer, when it is on (``profile()`` open or
      TPUNET_TRACE_DIR set): the span lands in this rank's
      ``tpunet-trace-rank<R>.json`` beside the request and collective phase
      spans, on their clock (CLOCK_MONOTONIC), with args ``seq`` (a
      per-process count of ROOT spans; children carry their root's),
      ``parent`` (the enclosing span's name), ``nbytes`` and, when given,
      ``kind``, ``step`` (from ``step_num``) and ``chunk``. Other ``args``
      reach only the profiler. Never ``comm_id``/``coll_seq``: those mark collective
      phases for merge_traces() and the ring's readers.
    - the JAX profiler, always, but only in a process that has imported
      jax already: ``jax.profiler.TraceAnnotation("tpunet:" + name,
      **args)`` (``StepTraceAnnotation`` when ``step_num`` is given), so
      under ``jax.profiler.trace`` the span shares the device operations'
      timeline, from whatever thread ran it. A root span also carries
      ``seq`` there: with both sinks on, the two copies of a root span are
      the clock anchors between the two files.

    With both off the cost is a thread-local read, a flag test and a no-op
    TraceMe: no native call, nothing kept.
    """

    __slots__ = ("name", "args", "_outer", "_seq", "_ann", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        outer = self._outer = getattr(_span_local, "top", None)
        args = self.args
        if outer is None:
            self._seq = next(_span_seq)
            args = dict(args, seq=self._seq)
        else:
            self._seq = outer._seq
        _span_local.top = self
        jax = sys.modules.get("jax")
        self._ann = None
        if jax is not None:
            kind = (jax.profiler.StepTraceAnnotation if "step_num" in args
                    else jax.profiler.TraceAnnotation)
            self._ann = kind("tpunet:" + self.name, **args)
        self._t0 = time.monotonic_ns() if _native_spans else 0
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        global _native_spans
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        t1 = time.monotonic_ns()
        _span_local.top = self._outer
        if not (self._t0 and _native_spans):
            return
        # Both ends floored to the tracer's microseconds, so that a child
        # never sticks out of its parent by a rounding.
        start_us = self._t0 // 1000
        kind = self.args.get("kind")
        rc = _native.load().tpunet_c_trace_span(
            self.name.encode(), start_us, t1 // 1000 - start_us, self._seq,
            int(self.args.get("nbytes", 0)),
            self._outer.name.encode() if self._outer is not None else None,
            str(kind).encode() if kind is not None else None,
            int(self.args.get("step_num", -1)), int(self.args.get("chunk", -1)))
        if rc == 0:  # the native tracer is off for this rank: stop asking
            _native_spans = False
        elif rc < 0 and exc_type is None:
            _native.check(rc, "trace_span")


_BRIDGE_KINDS = {"all_reduce": 0, "all_reduce_start": 1, "all_reduce_finish": 2,
                 "all_gather": 3, "reduce_scatter": 4, "all_to_all": 5,
                 "broadcast": 6, "neighbor_exchange": 7}


def bridge_call(kind: str, nbytes: int) -> None:
    """Count one host callback of the DCN bridge (tpunet/interop.py's
    io_callback path) and its operand bytes into
    ``tpunet_bridge_calls_total{kind=...}`` and
    ``tpunet_bridge_bytes_total{kind=...}``."""
    if kind not in _BRIDGE_KINDS:
        raise ValueError(
            f"kind must be one of {sorted(_BRIDGE_KINDS)}, got {kind!r}")
    lib = _native.load()
    _native.check(lib.tpunet_c_bridge_call(_BRIDGE_KINDS[kind], max(0, int(nbytes))),
                  "bridge_call")


def bridge_chunks(kind: str, chunks: int, in_flight: int) -> None:
    """Count the chunks of one boundary exchange (tpunet/interop.py's
    host_all_reduce) into ``tpunet_bridge_chunks_total{kind=...}`` and keep
    the most that were in flight at one time in
    ``tpunet_bridge_chunks_in_flight_max{kind=...}``."""
    _native.check(_native.load().tpunet_c_bridge_chunks(
        _BRIDGE_KINDS[kind], int(chunks), int(in_flight)), "bridge_chunks")


def bridge_minor_faults(kind: str, faults: int) -> None:
    """Count the minor page faults the process took across one boundary
    exchange (``resource.getrusage(RUSAGE_SELF).ru_minflt`` at the two ends
    of host_all_reduce's ``dcn.bridge`` span: every thread's, the runtime's
    copy threads among them) into
    ``tpunet_bridge_minor_faults_total{kind=...}``."""
    _native.check(_native.load().tpunet_c_bridge_minor_faults(
        _BRIDGE_KINDS[kind], max(0, int(faults))), "bridge_minor_faults")


def _coll_tags(events: list[dict]) -> dict[tuple, int]:
    """(comm_id, coll_seq, name) -> start ts for collective phase spans."""
    tags = {}
    for ev in events:
        args = ev.get("args") or {}
        if "comm_id" in args and "coll_seq" in args and "ts" in ev:
            key = (args["comm_id"], args["coll_seq"], ev.get("name", ""))
            # Keep the earliest occurrence (phases are unique per rank anyway).
            if key not in tags:
                tags[key] = ev["ts"]
    return tags


def _rank_host(events: list[dict]) -> str | None:
    """Host id of a rank file: the ``host`` tag the native tracer stamps on
    collective phase spans (a hex string of utils.h HostId())."""
    for ev in events:
        h = (ev.get("args") or {}).get("host")
        if h:
            return str(h)
    return None


def merge_traces(trace_dir: str, out_path: str | None = None) -> str:
    """Join every per-rank Chrome-trace JSON in `trace_dir` into ONE
    Perfetto-loadable timeline and return its path.

    Ranks on one host already share the monotonic clock; across hosts the
    clocks are unrelated, so per-rank timelines are aligned on the collective
    phase tags ``(comm_id, coll_seq, phase)``: the earliest tag common to all
    ranks becomes the anchor, and every rank is shifted so its anchor span
    starts at the same instant (the straggler-analysis convention — skew
    WITHIN a collective is preserved, clock offset is not mistaken for it).
    Files without common tags (point-to-point-only traces) merge unshifted.

    Track grouping: phase spans carry a ``host`` tag (HostId()), so ranks
    sharing a host group under ONE Perfetto process track ("host <id>") with
    per-rank thread tracks inside it, instead of interleaving W top-level
    groups — the view that makes an intra-host SHM stage vs inter-host DCN
    stage split readable. Traces from builds without the tag keep the old
    per-rank pid layout.

    Flight-recorder dumps (``tpunet-flightrec-rank*.json``, docs/DESIGN.md
    §6c) present in the directory merge too: each rank's events render as
    instant events on a dedicated "flightrec" thread track inside that
    rank's host group, shifted by the same per-rank offset as its trace
    spans (the recorder stamps the same monotonic clock the tracer uses).
    A directory holding ONLY flightrec dumps — the post-hang case, where
    tracing was never on — still merges (unshifted)."""
    files = sorted(glob.glob(os.path.join(trace_dir, "tpunet-trace-rank*.json")))
    fr_files = sorted(
        glob.glob(os.path.join(trace_dir, "tpunet-flightrec-rank*.json")))
    if not files and not fr_files:
        raise FileNotFoundError(
            f"no tpunet-trace-rank*.json or tpunet-flightrec-rank*.json "
            f"files in {trace_dir}")
    per_rank: list[list[dict]] = []
    ranks: list[int] = []
    for fi, path in enumerate(files):
        with open(path) as f:
            per_rank.append(json.load(f))
        m = re.search(r"rank(\d+)\.json$", path)
        ranks.append(int(m.group(1)) if m else fi)
    # Alignment: anchor on the earliest (comm_id, coll_seq, phase) present in
    # EVERY rank's file; shift each rank so anchors coincide at the max.
    tag_maps = [_coll_tags(events) for events in per_rank]
    common = set(tag_maps[0]) if tag_maps else set()
    for tm in tag_maps[1:]:
        common &= set(tm)
    offsets = [0] * len(per_rank)
    if common and len(per_rank) > 1:
        anchor = min(common, key=lambda k: (k[1], k[2]))  # lowest coll_seq
        target = max(tm[anchor] for tm in tag_maps)
        offsets = [target - tm[anchor] for tm in tag_maps]
    # Flight-recorder dumps are loaded up front so their host ids take part
    # in the host-grouping decision (post-hang merges often have ONLY dumps).
    fr_dumps: list[tuple[int, dict]] = []
    for path in fr_files:
        with open(path) as f:
            dump = json.load(f)
        m = re.search(r"rank(\d+)\.json$", path)
        fr_dumps.append((int(m.group(1)) if m else int(dump.get("rank", 0)),
                         dump))
    hosts = [_rank_host(events) for events in per_rank]
    group_by_host = any(h is not None for h in hosts) or \
        any(d.get("host") for _, d in fr_dumps)
    host_order: list[str] = []
    if group_by_host:
        for h in hosts:
            key = h if h is not None else "?"
            if key not in host_order:
                host_order.append(key)
    merged: list[dict] = []
    if group_by_host:
        for pid, host in enumerate(host_order, start=1):
            merged.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": f"host {host}"}})
    for events, off, host, rank in zip(per_rank, offsets, hosts, ranks):
        pid = host_order.index(host if host is not None else "?") + 1 \
            if group_by_host else None
        for ev in events:
            if group_by_host and ev.get("ph") == "M" and \
                    ev.get("name") == "process_name":
                continue  # replaced by the per-host group metadata above
            if off and "ts" in ev or group_by_host:
                ev = dict(ev)
            if off and "ts" in ev:
                ev["ts"] = ev["ts"] + off
            if group_by_host:
                # One process group per host; rank-disambiguated thread ids
                # inside it (native tids are small: comm ids / stream idx).
                ev["pid"] = pid
                ev["tid"] = rank * 1_000_000 + int(ev.get("tid", 0))
            merged.append(ev)
    # Flight-recorder dumps ride the same timeline: instant events on a
    # per-rank "flightrec" thread track, reusing the offset computed from
    # that rank's trace file (same monotonic clock on the same host).
    rank_offsets = dict(zip(ranks, offsets))
    for rank, dump in fr_dumps:
        off = rank_offsets.get(rank, 0)
        host = dump.get("host")
        if group_by_host:
            key = str(host) if host else "?"
            if key not in host_order:
                host_order.append(key)
                merged.append({"name": "process_name", "ph": "M",
                               "pid": len(host_order),
                               "args": {"name": f"host {key}"}})
            pid = host_order.index(key) + 1
            tid = rank * 1_000_000 + 999_999
        else:
            pid, tid = rank, 999_999
        merged.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": f"flightrec rank {rank}"}})
        for ev in dump.get("events", []):
            label = ev.get("kind", "?")
            if ev.get("name"):
                label = f"{label}:{ev['name']}"
            merged.append({
                "name": label, "ph": "i", "s": "t",
                "ts": ev.get("t", 0) + off, "pid": pid, "tid": tid,
                "args": {k: ev[k] for k in ("a", "b", "c", "d") if k in ev},
            })
    out_path = out_path or os.path.join(trace_dir, "tpunet-trace-merged.json")
    with open(out_path, "w") as f:
        json.dump(merged, f)
    return out_path


def scrape(port: int | None = None, host: str = "127.0.0.1", timeout: float = 5.0) -> str:
    """GET the native on-demand /metrics listener (TPUNET_METRICS_PORT) and
    return the exposition text — what a Prometheus scraper would see. With
    no explicit port, falls back to the env var and then to the natively
    bound port (metrics_port()) — which covers the ephemeral-port case
    (TPUNET_METRICS_PORT=0)."""
    if port is None:
        port = int(os.environ.get("TPUNET_METRICS_PORT", "0") or "0")
    if not port:
        port = metrics_port()
    if not port:
        raise ValueError("no port given, TPUNET_METRICS_PORT unset, and no "
                         "native /metrics listener is bound")
    with urllib.request.urlopen(f"http://{host}:{port}/metrics", timeout=timeout) as r:
        return r.read().decode()
