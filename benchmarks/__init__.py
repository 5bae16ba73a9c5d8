"""Shared multiprocess launch harness for the benchmark entrypoints."""

from __future__ import annotations

import math
import multiprocessing as mp
import os
import queue
import socket
import sys
import threading
import time
from pathlib import Path


REPO_ROOT = Path(__file__).resolve().parent.parent


def place_compile_cache() -> str:
    """Decide where JAX's persistent compilation cache lives, before the
    first compile. Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it
    itself and nothing is set here. Where it is not, the cache goes to
    `<checkout>/.jax_cache`: a fixed path, because the path is part of the
    cache key, so a directory named after a pid, a time or a temporary name
    never hits. The choice is written to the environment, so child
    processes inherit it. Returns the directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        return cache_dir
    cache_dir = str(REPO_ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before us: the env was read already
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def claim_device(platform: str | None = None) -> dict:
    """The one way a benchmark entry point reaches JAX. Returns
    {"platform", "device_kind", "device_count"} as JAX reports them; every
    JSON line a tool prints carries these three.

    platform "cpu": hold this process to the CPU backend (tests of the
    tools, and loopback ranks, which cannot share one chip).
    platform "tpu" or None: the device that answers must be a TPU. Anything
    else exits: a measurement path that finds no chip fails, it does not
    fall back. The compile cache is placed on this path only; CPU runs are
    tests and their compiles are small."""
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        place_compile_cache()
        import jax
    devices = jax.devices()
    found = devices[0].platform
    if platform != "cpu" and found != "tpu":
        raise SystemExit(
            f"this run needs a TPU and JAX answered with {found!r} "
            f"({devices[0].device_kind}); pass --platform cpu only to test "
            "the tool itself")
    return {"platform": found, "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def chained_step_time(step_fn, state, args, warmup: int, iters: int) -> float:
    """Per-step seconds for a `state, loss = step_fn(state, *args)` train
    step: `state` is threaded through `iters` chained steps (the step
    donates it, so the same state cannot be passed twice) and the clock
    stops on a host transfer of the final loss, which depends on every step
    of the chain through `state`. Dispatch is asynchronous, so per-step
    host overhead hides under the device work the way it does in a real
    training loop. `jax.block_until_ready` on the final loss waits for the
    device just as the transfer does (chip_smoke.py's train phase times a
    dependent matmul chain both ways); the transfer is used because the
    finiteness check needs the value on the host anyway.
    """
    for _ in range(max(warmup, 1)):
        state, loss = step_fn(state, *args)
    if not math.isfinite(float(loss)):  # hard sync: warmup/compile complete
        raise RuntimeError("non-finite loss in benchmark warmup")
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step_fn(state, *args)
    final = float(loss)  # single chain-wide sync
    dt = (time.perf_counter() - t0) / iters
    if not math.isfinite(final):
        raise RuntimeError("non-finite loss in benchmark")
    return dt


def run_json_lines(argv: list, timeout_s: float,
                   cwd: str | None = None) -> tuple[list, str]:
    """Run `python <argv...>` and parse every JSON-object line it printed.

    Returns (rows, "") on success or ([], error-tail) when the tool timed
    out, exited nonzero, or printed no JSON (mfu_sweep prints one line per
    config, the other tools one line).
    """
    import json
    import subprocess

    try:
        p = subprocess.run([sys.executable] + list(argv), capture_output=True,
                           text=True, timeout=timeout_s, cwd=cwd)
    except subprocess.TimeoutExpired:
        return [], f"timed out after {timeout_s}s"
    rows = []
    if p.returncode == 0 and p.stdout.strip():
        for line in p.stdout.strip().splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    if not rows:
        return [], (p.stderr or "no JSON output")[-500:]
    return rows, ""


def pump_lines(proc) -> queue.Queue:
    """The lines of a child's stdout as they come, then None at EOF, on a
    queue: a queue can be waited on with a timeout, a pipe cannot."""
    lines: queue.Queue = queue.Queue()

    def pump():
        for line in proc.stdout:
            lines.put(line)
        lines.put(None)

    threading.Thread(target=pump, daemon=True).start()
    return lines


def iqr(xs) -> float | None:
    """Interquartile range, np.percentile linear-interpolation definition
    — THE one definition every benchmark reports (serve_bench, engine_p2p,
    bench.py), so cross-bench IQR columns are comparable. None when fewer
    than 4 samples (a 'spread' of 2-3 points is noise about noise)."""
    import numpy as np

    if len(xs) < 4:
        return None
    return float(np.percentile(xs, 75) - np.percentile(xs, 25))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def check_rank_results(results: dict) -> dict:
    """For workers posting (status, payload): raise if any rank failed,
    else return {rank: payload}. Shared by the benchmark entrypoints."""
    for rank, (status, _) in sorted(results.items()):
        if status != "OK":
            raise SystemExit(f"rank {rank} failed: {status}")
    return {rank: payload for rank, (_, payload) in results.items()}


def spawn_ranks(target, world: int, extra_args=(), timeout: float = 600.0) -> dict:
    """Spawn `world` processes running target(rank, world, port, queue, *extra).

    Each worker must post (rank, payload) to the queue exactly once. Returns
    {rank: payload}. Workers are always joined/killed, even if a rank dies
    without reporting (a native-layer crash posts nothing).
    """
    import queue as queue_mod

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    port = free_port()
    procs = [
        ctx.Process(target=target, args=(r, world, port, q) + tuple(extra_args))
        for r in range(world)
    ]
    for p in procs:
        p.start()
    results: dict = {}
    try:
        for _ in range(world):
            try:
                rank, payload = q.get(timeout=timeout)
            except queue_mod.Empty:
                break  # diagnosed below with exit codes, not a raw traceback
            results[rank] = payload
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
                p.join()  # reap, so exitcode below reads -SIGKILL, not None
    if len(results) < world:
        missing = sorted(set(range(world)) - results.keys())
        codes = {r: procs[r].exitcode for r in missing}
        raise SystemExit(
            f"ranks {missing} never reported within {timeout}s "
            f"(exit codes {codes}) — native-layer crash or hang?")
    return results
