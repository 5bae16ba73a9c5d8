"""What every adapter shares: the clock, the device, the compile cache, the
table of peaks, the profiler window, the per-layer readers and the result
line. A run that finds no TPU, or fewer chips than its cell asks for, ends
here with a non-zero exit code and no result.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "perfbench"
KERNEL = 'custom_call_target="tpu_custom_call"'


def process_start() -> float:
    """time.time() at the start of the run's first process; children get it
    through the environment, so that setup_s counts from there."""
    t0 = os.environ.get("PERFBENCH_T0")
    if t0 is None:
        t0 = repr(time.time())
        os.environ["PERFBENCH_T0"] = t0
    return float(t0)


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(name: str) -> dict:
    """The cell's entry in BENCHMARK.json, its own file, its configuration
    and its traffic mix, found by name."""
    entry = next((w for w in manifest()["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    out = dict(load("workloads", name))
    out.update(name=name, chips=entry["chips"], config_name=entry["config"],
               traffic_name=entry["traffic"])
    out["config"] = load("configs", entry["config"])
    out["traffic"] = load("traffic", entry["traffic"])
    return out


def metric_names(cell_name: str, traced: bool) -> list[str]:
    """The metrics this cell reports: end to end with the trace off, per
    layer with it on."""
    m = manifest()
    e2e = [x for x in m["end_to_end"]
           if "workloads" not in x or cell_name in x["workloads"]]
    if not traced:
        return [x["name"] for x in e2e]
    reported = {x["name"] for x in e2e}
    return [x["name"] for x in m["per_layer"]
            if (cell_name in x["workloads"] if "workloads" in x
                else x["moves"] in reported)]


def units() -> dict:
    m = manifest()
    return {x["name"]: x["unit"] for x in m["end_to_end"] + m["per_layer"]}


# -- the device ---------------------------------------------------------------

def place_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else <checkout>/.jax_cache. A fixed path: it is part of the key."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(ROOT / ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    import jax

    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d


def claim_device(chips: int, platform: str = "tpu") -> dict:
    """The devices this process runs on, as JAX reports them. platform
    "cpu" exists for the twin ranks of a cell and for perfbench/tests; no
    option of the command reaches it."""
    t = time.perf_counter()
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        place_cache()
        import jax
    devs = jax.devices()
    print(f"[perfbench] device reached {time.time() - process_start():.2f}s after "
          f"the run's start ({time.perf_counter() - t:.2f}s in JAX)",
          file=sys.stderr, flush=True)
    if platform != "cpu" and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"perfbench needs {chips} TPU chip(s); JAX answered with "
            f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table or kind.startswith("_"):
        raise SystemExit(f"no peaks recorded for device kind {kind!r}; add it "
                         "to perfbench/peaks.json with its source")
    return table[kind]


def memory_peak() -> tuple[int, dict]:
    """(peak bytes on the fullest chip, the two statistics it is the sum of).

    This runtime keeps two books. peak_bytes_in_use counts the buffers the
    process holds (state, batches); the scratch of the program that runs is
    reserved "at the bottom of memory" when the program is loaded and is
    counted under peak_bytes_reserved alone: 5.37 GB for the VGG16 step
    (the compiler states 5.38 GB of temporaries), 4.57 GB for the Mistral
    step (4.75 GB). PR 23 showed on the chip that the two do not overlap: a
    step is refused as soon as a ballast buffer leaves less room than the
    reservation beside what is in use (PERF.md section 2)."""
    import jax

    best, parts = 0, {}
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        used = int(st.get("peak_bytes_in_use", 0))
        reserved = int(st.get("peak_bytes_reserved", 0))
        if used + reserved >= best:
            best = used + reserved
            parts = {"peak_bytes_in_use": used, "peak_bytes_reserved": reserved}
    return best, parts


def count_kernels(compiled, least: int | None) -> int:
    """Pallas kernels in the executable that will run. The cell's file
    states the LEAST a sound program holds (every forward kernel once); one
    that runs some of them again, as remat's recompute does, holds more, and
    how often each ran is the trace's to say (readers/kernel_roofline.py). A
    program built by the interpreter, or one whose shapes fell back to plain
    einsums, holds fewer and is refused before a window opens."""
    n = compiled.as_text().count(KERNEL)
    if least is not None and n < least:
        raise SystemExit(f"the compiled program holds {n} tpu_custom_call "
                         f"kernels, the cell's file says at least {least}")
    return n


def release() -> None:
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# -- the traced window --------------------------------------------------------

def annotate(name: str):
    """A host span in the profiler's own trace, written by the benchmark
    around its calls into a layer."""
    import jax

    return jax.profiler.TraceAnnotation("pb:" + name)


class TraceWindow:
    """Profiler on for a part of the measured window. start() and stop()
    are called by the adapter at step or request boundaries; between them
    the annotation "window" spans the traced time."""

    def __init__(self, on: bool, cell_name: str, seed: int):
        self.on = on
        self.dir = str(ROOT / "chiprun_out" / "perfbench" / f"{cell_name}-{seed}")
        self.started = self.stopped = False
        self._span = None
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if not self.on or self.started:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._span = annotate("window")
        self._span.__enter__()
        self.t_start = time.perf_counter()
        self.started = True

    def stop(self) -> None:
        if not self.started or self.stopped:
            return
        import jax

        self.t_stop = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True

    def reduce(self, patterns: dict | None = None):
        """(Trace, lo, hi) once stopped. cleanup() removes the raw files once
        the readers are done: a trace is large and the host keeps what is
        written."""
        from perfbench import trace

        path = trace.find_xplane(self.dir)
        t = trace.load(path, **(patterns or {}))
        lo, hi = trace.window_of(t)
        return t, lo, hi

    def cleanup(self) -> None:
        if self.on and os.environ.get("PERFBENCH_KEEP_TRACE") != "1":
            shutil.rmtree(self.dir, ignore_errors=True)


def per_layer(cell_name: str, ctx: dict) -> dict:
    """Run the reader of every per-layer metric this cell reports. A reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    unit = units()
    for name in metric_names(cell_name, traced=True):
        spec = load("metrics", name)
        reader = importlib.import_module(f"perfbench.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": unit[name]}
    return out


def breakdown(t, lo: float, hi: float) -> dict:
    from perfbench import trace

    return {"device_ops": trace.top_ops(t, lo, hi),
            "idle_gaps": trace.idle_gaps(t, lo, hi)}


# -- the result ---------------------------------------------------------------

def result(correct: bool, attempted: int, failed: int, metrics: dict,
           device: dict, compared: dict, breakdown: dict | None = None,
           extra: dict | None = None) -> dict:
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if extra:
        out["notes"] = extra
    out["compared"] = compared  # last: the driver keeps the line's end
    return out


REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def check_line(res: dict, traced: bool) -> None:
    """What the driver reads of a result line, asserted; the tests of every
    adapter share it."""
    keys = list(res)
    assert keys[:5] == REQUIRED and keys[-1] == "compared"
    assert set(keys) <= set(REQUIRED) | {"breakdown", "notes", "compared"}
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    if traced:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    for c in res["compared"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def emit(res: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in res["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)


@contextlib.contextmanager
def stage(name: str):
    """Progress on standard error, with seconds: what a failed call's tail
    shows first."""
    t = time.perf_counter()
    print(f"[perfbench] {name} ...", file=sys.stderr, flush=True)
    yield
    print(f"[perfbench] {name}: {time.perf_counter() - t:.2f}s",
          file=sys.stderr, flush=True)
