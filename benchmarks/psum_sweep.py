"""JAX-tier AllReduce sweep: `dcn_psum` inside jit over the tpunet transport.

BASELINE config 2 ("JAX pmap(lax.psum)-style AllReduce sweep 8 B - 128 MB
over the new DCN transport"): measures the full path a training step pays —
jitted program -> XLA FFI custom call (zero-copy; round 5) -> ring
collectives -> multi-stream engine — vs `benchmarks.busbw_sweep --op
allreduce`, which measures the native collectives alone; the difference is
the JAX-integration tax. --no-ffi forces the legacy io_callback bridge
(the round-4 path: ~3 full-buffer staging copies per call) for A/B.

    python -m benchmarks.psum_sweep -n 2 --nstreams 4 -b 1K -e 64M
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from benchmarks import spawn_ranks
from benchmarks.busbw_sweep import make_table_emitter, parse_size, sweep_sizes


def _worker(rank, world, port, q, args):
    try:
        from benchmarks import claim_device

        claim_device("cpu")  # loopback ranks cannot share one TPU
        os.environ["TPUNET_NSTREAMS"] = str(args.nstreams)
        if args.no_ffi:
            os.environ["TPUNET_FFI_COLLECTIVES"] = "0"
        import jax
        import jax.numpy as jnp

        from tpunet import distributed
        from tpunet.interop import dcn_psum

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        fn = jax.jit(dcn_psum)
        rows = []
        for nbytes in sweep_sizes(args.begin, args.end, args.factor):
            count = max(nbytes // 4, 1)
            x = jnp.full((count,), float(rank + 1), jnp.float32)
            iters = args.iters if nbytes >= (1 << 16) else args.iters * 4
            comm = distributed.global_communicator()
            for _ in range(args.warmup):
                fn(x).block_until_ready()
            comm.barrier()
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            out.block_until_ready()
            # Closing barrier before reading the clock, matching the
            # busbw_sweep baseline loop — the reported delta between the two
            # IS the JAX-integration tax, so methodology must match.
            comm.barrier()
            dt = (time.perf_counter() - t0) / iters
            expect = float(sum(r + 1 for r in range(world)))
            assert float(out[0]) == expect, f"bad psum result {out[0]} != {expect}"
            rows.append((count * 4, count, dt))
        distributed.finalize()
        q.put((rank, ("OK", rows)))
    except Exception as e:  # noqa: BLE001
        q.put((rank, (f"FAIL: {type(e).__name__}: {e}", [])))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--world", type=int, default=2)
    ap.add_argument("--nstreams", type=int, default=4)
    ap.add_argument("-b", "--begin", type=parse_size, default=8)
    ap.add_argument("-e", "--end", type=parse_size, default=128 << 20)
    ap.add_argument("-f", "--factor", type=int, default=2)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--json", default="", help="also dump rows to this file")
    ap.add_argument("--no-ffi", action="store_true",
                    help="force the io_callback bridge instead of the "
                         "zero-copy XLA FFI custom call (A/B baseline)")
    args = ap.parse_args(argv)

    from benchmarks import check_rank_results

    results = check_rank_results(
        spawn_ranks(_worker, args.world, extra_args=(args,), timeout=3600)
    )
    emit = make_table_emitter("psum", nstreams=args.nstreams, json_path=args.json)
    emit(results[0], args.world)


if __name__ == "__main__":
    main(sys.argv[1:])
