"""tpunet headline benchmark (driver entry).

Measures the framework's headline metric — ring AllReduce bus bandwidth over
the multi-stream DCN transport — in the reference's own terms: a 128 MiB
AllReduce between 2 ranks, multi-stream engine vs the single-stream baseline
(the configuration stock NCCL-TCP / gRPC-DCN uses one connection per peer;
reference headline: +50% AllReduce throughput from multi-stream striping,
reference README.md:50).

Prints ONE JSON line:
  {"metric": "allreduce_busbw_128MiB",
   "value": <GB/s, MEDIAN of the winning config over the paired reps>,
   "unit": "GB/s",
   "vs_baseline": <median multi-stream / median single-stream>,
   "value_iqr"/"baseline_iqr": <GB/s spread over the reps>, "reps": N,
   "best_config": <sweep key>, "sweep": {<config>: GB/s, ...},
   "analysis": "PERF_NOTES.md",
   "kernels": {...}, "model_tier": {"platform": "tpu", "device_kind": ...,
                  "tokens_per_s": N, "mfu": N, "vgg_img_per_s": N, ...},
   "decode": {...}}
Round-5 methodology (verdict item 6): a sweep picks the winning
multi-stream config — each config measured SWEEP_REPS (3) times and
compared by MEDIAN, because a single-shot winner on this box is
noise-picked (±20% run-to-run band) and the dispatch tables busbw_sweep
seeds inherit whatever the sweep blesses — then TPUNET_BENCH_REPS
(default 10) PAIRED, INTERLEAVED winner/baseline runs produce medians +
IQRs; interleaving puts slow drift on both sides of the ratio.

busbw follows the nccl-tests definition for AllReduce: 2*(W-1)/W * bytes / t.

The device tiers (kernel smoke, benchmarks.tpu_headline, one decode point)
run FIRST, each in a subprocess of its own that holds the chip for its
lifetime (this parent never imports JAX, so it never holds it). They run on
a TPU or the whole bench exits non-zero: there is no CPU tier, no demotion
of a failed kernel and no replay of an older capture.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmarks import spawn_ranks

NBYTES = 128 << 20  # 128 MiB, the top of the reference's sweep (-e 128M)
WORLD = 2
WARMUP = 2
ITERS = 6
MULTI_NSTREAMS = 4


def _worker(rank: int, world: int, port: int, q, nstreams: int,
            extra_env: dict | None = None) -> None:
    try:
        os.environ["TPUNET_NSTREAMS"] = str(nstreams)
        os.environ.setdefault("TPUNET_MIN_CHUNKSIZE", str(1 << 20))
        for k, v in (extra_env or {}).items():
            os.environ[k] = str(v)
        import numpy as np

        from tpunet.collectives import Communicator

        comm = Communicator(
            coordinator=f"127.0.0.1:{port}", rank=rank, world_size=world
        )
        n = NBYTES // 4
        times = []
        for it in range(WARMUP + ITERS):
            arr = np.full(n, float(rank + 1), dtype=np.float32)
            comm.barrier()
            t0 = time.perf_counter()
            out = comm.all_reduce(arr, inplace=True)
            dt = time.perf_counter() - t0
            if it >= WARMUP:
                times.append(dt)
        expect = float(sum(r + 1 for r in range(world)))
        if out[0] != expect or out[-1] != expect:
            raise RuntimeError(f"allreduce wrong result: {out[0]} != {expect}")
        comm.close()
        q.put((rank, ("OK", times)))
    except Exception as e:  # surface the failure to the parent
        q.put((rank, (f"ERR: {e!r}", [])))


def _run_config(nstreams: int, extra_env: dict | None = None) -> float:
    """Returns busbw in GB/s (best iteration, nccl-tests convention)."""
    from benchmarks import check_rank_results

    results = check_rank_results(
        spawn_ranks(_worker, WORLD, extra_args=(nstreams, extra_env), timeout=300)
    )
    # Per iteration both ranks measure the same collective; use the max of the
    # per-rank times (the collective isn't done until the slowest rank is),
    # then the best iteration, as nccl-tests does with its min/avg columns.
    per_iter = [
        max(results[r][i] for r in range(WORLD)) for i in range(ITERS)
    ]
    best = min(per_iter)
    busbw_factor = 2.0 * (WORLD - 1) / WORLD
    return busbw_factor * NBYTES / best / 1e9


def _device_tool(argv: list[str], timeout_s: int) -> dict:
    """Run one device-tier tool in its own process and return the JSON line
    it printed. The tool failing, printing nothing, or having run on
    anything but a TPU ends the bench."""
    from benchmarks import run_json_lines

    rows, err = run_json_lines(argv, timeout_s)
    if not rows:
        raise SystemExit(f"[bench] {' '.join(argv)} failed: {err}")
    row = rows[-1]
    if row.get("platform") != "tpu":
        raise SystemExit(f"[bench] {' '.join(argv)} ran on "
                         f"{row.get('platform')!r}, not a TPU: {row}")
    print(f"[bench] {argv[1]}: {row}", file=sys.stderr)
    return row


def _kernel_smoke() -> dict:
    """Per-kernel compile+run probe: exits non-zero itself unless every
    kernel is "ok", so a Mosaic rejection is named before the model tier."""
    return _device_tool(["-m", "benchmarks.kernel_smoke"], 600)


def _model_tier() -> dict:
    # Generous: the chip-sized headline model (735M params) compiles for a
    # while before its ~8s of steps.
    return _device_tool(["-m", "benchmarks.tpu_headline"], 2400)


def _decode_tier() -> dict:
    """Inference tier: one on-chip decode number (GQA, the KV-cache
    capability's headline config)."""
    return _device_tool(
        ["-m", "benchmarks.decode_bench",
         "--d", "2048", "--layers", "12", "--heads", "16", "--ff", "8192",
         "--batch", "8", "--prompt", "512", "--new", "128",
         "--kv-heads", "4"], 1500)


def main() -> None:
    from benchmarks import place_compile_cache

    place_compile_cache()  # the tools below inherit it through the env
    kernels = _kernel_smoke()
    model_tier = _model_tier()
    decode = _decode_tier()

    # Make sure the native library exists before timing anything.
    from tpunet import _native

    _native.build_native()

    # In-bench mini-sweep: the best multi-stream configuration, not just the
    # fixed default — on many-core hosts striping wins, on this 1-core
    # sandbox all configs tie at the wire ceiling (analysis: PERF_NOTES.md).
    multi_cfgs = [
        (MULTI_NSTREAMS, None),
        (2, None),
        (MULTI_NSTREAMS, {"TPUNET_RING_CHUNKSIZE": 2 << 20}),
    ]
    import statistics

    # Median of SWEEP_REPS per config: a single-shot winner is noise-picked
    # on this box (±20% band vs a few-% config effect), and the winner feeds
    # both the headline's paired reps AND the methodology the dispatch-table
    # sweep (busbw_sweep --emit-dispatch) copies.
    SWEEP_REPS = 3
    sweep = {}
    cfg_by_key = {}
    for ns, extra in multi_cfgs:
        key = f"ns{ns}" + ("_chunk2M" if extra else "")
        sweep[key] = statistics.median(
            _run_config(ns, extra) for _ in range(SWEEP_REPS))
        cfg_by_key[key] = (ns, extra)
    best_key = max(sweep, key=sweep.get)
    best_ns, best_extra = cfg_by_key[best_key]
    # Paired interleaved reps of winner vs single-stream baseline:
    # medians + IQRs instead of a single best-of sample (the box's ±20%
    # run-to-run band was wider than every effect measured on it).
    reps = max(int(os.environ.get("TPUNET_BENCH_REPS", "10")), 1)
    best_runs, base_runs = [], []
    for rep in range(reps):
        best_runs.append(_run_config(best_ns, best_extra))
        base_runs.append(_run_config(nstreams=1))
        print(f"[bench] rep {rep}: {best_key} {best_runs[-1]:.3f} GB/s, "
              f"ns1 {base_runs[-1]:.3f} GB/s", file=sys.stderr)

    def _iqr(xs):
        from benchmarks import iqr as _shared_iqr

        spread = _shared_iqr(xs)
        return round(spread, 3) if spread is not None else None

    best = statistics.median(best_runs)
    baseline = statistics.median(base_runs)
    best_iqr, base_iqr = _iqr(best_runs), _iqr(base_runs)
    print(
        f"[bench] medians over {reps} paired reps: single-stream "
        f"{baseline:.3f} GB/s (IQR {base_iqr}), {best_key} {best:.3f} GB/s "
        f"(IQR {best_iqr}) -> {best / baseline:.2f}x",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "allreduce_busbw_128MiB",
                "value": round(best, 3),
                "unit": "GB/s",
                "vs_baseline": round(best / baseline, 3),
                "value_iqr": best_iqr,
                "baseline_gbps": round(baseline, 3),
                "baseline_iqr": base_iqr,
                "reps": reps,
                "best_config": best_key,
                "sweep": {k: round(v, 3) for k, v in sweep.items()},
                "sweep_reps": SWEEP_REPS,
                "analysis": "PERF_NOTES.md",
                "kernels": kernels,
                "model_tier": model_tier,
                "decode": decode,
            }
        )
    )


if __name__ == "__main__":
    main()
