"""Metric registry checker.

The Prometheus catalogue lives in ``cpp/src/metrics.cc`` (``family(...)``
registrations, the ``size_hist``/``stage_hist`` helpers, and the
``TcpGaugeDef`` table); consumers live across the language boundary in
``tpunet/telemetry.py``, the tests, and the benchmarks. Invariants:

1. Every family is declared exactly once (a duplicated family emits a
   Prometheus exposition that fails text-format lint).
2. Names are ``tpunet_`` + snake_case with a recognized unit/kind suffix —
   or carry a NAMING_EXCEPTIONS entry with a reason (reference-compat names
   predate the convention).
3. Direct label sets are consistent: one family never emits with two
   different label-key sets (``le`` excluded, histogram ``_bucket``/``_sum``/
   ``_count`` series folded into their base family).
4. Every ``tpunet_*`` metric name referenced from the Python layer
   (telemetry module, telemetry/perf tests, engine benchmarks) exists in the
   C++ registry — the drift that turns dashboards silently blank.
"""

from __future__ import annotations

import re
from pathlib import Path

from tools.lint._util import read_text, strip_c_comments

# Recognized unit / kind suffixes (Prometheus naming conventions, adapted:
# byte counts, microseconds, bits-per-second, totals, and the reference's
# nbytes histogram spelling).
UNIT_SUFFIXES = (
    "_total",
    "_bytes",
    "_us",
    "_bps",
    "_nbytes",
    "_per_second",
)

# Families allowed to break the suffix rule; every entry needs a reason.
NAMING_EXCEPTIONS = {
    "tpunet_hold_on_request": "reference-compat gauge name (tokio:184-190)",
    "tpunet_failed_requests": "reference-compat counter name",
    "tpunet_stream_cwnd": "unit is TCP segments (tcpi_snd_cwnd), not a measure",
    "tpunet_stream_fairness_jain": "dimensionless Jain index in [0,1]",
    "tpunet_faults_injected": "label-less compat twin of tpunet_faults_injected_total",
    "tpunet_codec_wire_ratio": "dimensionless encoded/payload byte ratio in (0, 1]",
    "tpunet_serve_queue_depth": "instantaneous request count per serving tier (dimensionless gauge)",
    "tpunet_lane_weight": "dimensionless stripe weight (1..16) per lane in the WRR scheduler",
    "tpunet_world_size": "dimensionless rank count of the live communicator (churn gauge)",
    "tpunet_weight_version": "dimensionless checkpoint version stamp (hot-swap gauge)",
    "tpunet_bridge_chunks_in_flight_max": "dimensionless high-water count of a boundary exchange's chunks in flight",
}

_SNAKE = re.compile(r"^tpunet_[a-z0-9]+(?:_[a-z0-9]+)*$")
_FAMILY = re.compile(r'family\(\s*"(tpunet_[a-z0-9_]+)"')
_HIST_HELPER = re.compile(r'(?:size_hist|stage_hist)\(\s*"(tpunet_[a-z0-9_]+)"')
_GAUGE_TABLE = re.compile(r'\{\s*"(tpunet_[a-z0-9_]+)"\s*,\s*"(?:gauge|counter|histogram)"')
# Inside C++ string literals the label quotes are escaped (rank=\"%lld\"),
# so the label body may contain \" sequences but no bare quote.
_EMIT_LABELED = re.compile(r'"(tpunet_[a-z0-9_]+)\{((?:\\"|[^}"])*)\}')
_LABEL_KEY = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)=")
_PY_REF = re.compile(r'["\'](tpunet_[a-z0-9_]+)["\']')

# Python files whose tpunet_* string literals are treated as metric-name
# consumers. tpunet_c_* / tpunet_comm_* ABI symbols are filtered out.
_CONSUMER_FILES = (
    "tpunet/telemetry.py",
    "tests/test_telemetry.py",
    "tests/telemetry_smoke.py",
    "tests/perf_smoke.py",
    "benchmarks/engine_p2p.py",
)

_SERIES_SUFFIXES = ("_bucket", "_sum", "_count")

# Synthetic names fed to the Prometheus text PARSER's unit tests
# (tests/test_telemetry.py builds hand-written expositions to pin _LINE's
# grammar) — they are parser inputs, not references to real families.
PARSER_FIXTURES = {
    "tpunet_uptime_seconds",
    "tpunet_rate",
    "tpunet_bad_value",
    "tpunet_demo",
}


def registry_families(root: Path) -> set[str]:
    """The set of metric families registered in cpp/src/metrics.cc.

    Shared with tests/test_telemetry.py's registry-driven reset test: every
    family the C++ layer declares must sample zero after ``reset()`` (modulo
    a short, documented exception list) — generated from the same parse the
    lint checker uses, so a newly registered family is reset-covered on the
    day it lands or the test names it."""
    metrics_cc = Path(root) / "cpp" / "src" / "metrics.cc"
    return set(_registrations(strip_c_comments(read_text(metrics_cc))))


def _base_family(name: str) -> str:
    for suffix in _SERIES_SUFFIXES:
        if name.endswith(suffix):
            return name[: -len(suffix)]
    return name


def _registrations(text: str) -> list[str]:
    regs: list[str] = []
    for regex in (_FAMILY, _HIST_HELPER, _GAUGE_TABLE):
        regs.extend(regex.findall(text))
    return regs


def check_metric_registry(root: Path) -> list[str]:
    root = Path(root)
    metrics_cc = root / "cpp" / "src" / "metrics.cc"
    if not metrics_cc.is_file():
        return ["cpp/src/metrics.cc not found — metric registry unverifiable"]
    text = strip_c_comments(read_text(metrics_cc))
    regs = _registrations(text)
    registry = set(regs)
    violations: list[str] = []

    # 1. declared exactly once
    seen: set[str] = set()
    for name in regs:
        if name in seen:
            violations.append(f"metric family {name} is registered more than once in metrics.cc")
        seen.add(name)

    # 2. naming convention
    for name in sorted(registry):
        if not _SNAKE.match(name):
            violations.append(f"metric family {name} is not tpunet_ snake_case")
            continue
        if name.endswith(UNIT_SUFFIXES):
            continue
        if name not in NAMING_EXCEPTIONS:
            violations.append(
                f"metric family {name} has no unit suffix {UNIT_SUFFIXES} and no "
                f"NAMING_EXCEPTIONS entry in tools/lint/metricsreg.py"
            )

    # 3. direct label-set consistency (families emitted via %s format
    # helpers — histograms, the TCP gauge table — are uniform by
    # construction and not visible to this pass).
    label_sets: dict[str, set[frozenset[str]]] = {}
    emitted: set[str] = set()
    for name, labels in _EMIT_LABELED.findall(text):
        base = _base_family(name)
        emitted.add(base)
        keys = frozenset(k for k in _LABEL_KEY.findall(labels) if k != "le")
        label_sets.setdefault(base, set()).add(keys)
    for base, sets in sorted(label_sets.items()):
        if len(sets) > 1:
            pretty = " vs ".join(sorted("{" + ",".join(sorted(s)) + "}" for s in sets))
            violations.append(f"metric family {base} emits inconsistent label sets: {pretty}")

    # Emitted-but-never-registered (a family() call was dropped while its
    # emit survived → exposition lint failure at runtime).
    for base in sorted(emitted - registry):
        violations.append(f"metric {base} is emitted in metrics.cc but never registered via family()")

    # 4. cross-layer references resolve
    for rel in _CONSUMER_FILES:
        path = root / rel
        if not path.is_file():
            continue
        for name in sorted(set(_PY_REF.findall(read_text(path)))):
            if name.startswith(("tpunet_c_", "tpunet_comm_", "tpunet_xla_")):
                continue  # ABI symbols, not metrics
            if _base_family(name) in PARSER_FIXTURES:
                continue
            if _base_family(name) not in registry:
                violations.append(
                    f"{rel} references metric {name} which does not exist in the "
                    f"metrics.cc registry"
                )
    return violations
