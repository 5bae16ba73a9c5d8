"""VGG16 synthetic data-parallel training benchmark (img/s).

In-repo replacement for the reference's end-to-end benchmark — Bagua's
`synthetic_benchmark.py` VGG16 run (reference README.md:52-84: 4046.6 ± 205.2
img/s total on 32 V100 with the multi-stream transport vs 2744.9 ± 122.3
baseline). Same shape: synthetic ImageNet-sized batches, timed iterations,
img/s mean ± std, per-device and total.

Modes:
  Single process (default): DP over the local `jax.devices()` mesh — the
  in-pod tier; XLA inserts the gradient all-reduce over ICI.
      python -m benchmarks.vgg_synthetic --iters 5
  Multi-process (-n N): N ranks on 127.0.0.1, each running the jitted local
  step plus the cross-host DCN gradient tier over the tpunet transport
  (`make_train_step(cross_host=True)`) — the configuration whose scaling the
  reference's numbers measure. Total img/s sums ranks.
      python -m benchmarks.vgg_synthetic -n 2 --width-mult 0.125
"""

from __future__ import annotations

import argparse
import math
import os
import statistics
import sys
import time


def _build(args):
    import jax
    import jax.numpy as jnp
    import optax

    from tpunet.models import VGG, VGG16_CFG
    from tpunet.train import create_train_state, make_train_step, synthetic_batch

    model = VGG(
        cfg=VGG16_CFG,
        num_classes=args.classes,
        width_mult=args.width_mult,
        hidden=max(8, int(4096 * args.width_mult)),
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        classifier_dropout=0.0,
    )
    tx = optax.sgd(0.01, momentum=0.9)
    import numpy as np

    rng = np.random.default_rng(0)
    images, labels = synthetic_batch(rng, args.batch_size, args.image_size, args.classes)
    state, _ = create_train_state(
        model, jax.random.PRNGKey(0), jnp.asarray(images), tx
    )
    step = make_train_step(model, tx, cross_host=args.cross_host, donate=True)
    return state, step, jnp.asarray(images), jnp.asarray(labels)


def run_benchmark(args, emit=print):
    import jax

    state, step, images, labels = _build(args)
    rngkey = jax.random.PRNGKey(1)

    # Warmup (compile).
    loss = None
    for _ in range(args.warmup):
        state, loss = step(state, images, labels, rngkey)
    if loss is not None:
        loss.block_until_ready()

    rates = []
    for it in range(args.iters):
        t0 = time.perf_counter()
        for _ in range(args.batches_per_iter):
            state, loss = step(state, images, labels, rngkey)
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        rates.append(args.batch_size * args.batches_per_iter / dt)
        emit(f"Iter #{it}: {rates[-1]:.1f} img/sec")
    if not math.isfinite(float(loss)):
        raise RuntimeError("non-finite loss during benchmark")
    return rates


def _mp_worker(rank, world, port, q, argv):
    try:
        from benchmarks import claim_device

        claim_device("cpu")  # loopback ranks cannot share one TPU
        args = _parse(argv)
        from tpunet import distributed

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        args.cross_host = True
        rates = run_benchmark(args, emit=lambda *_: None)
        distributed.finalize()
        q.put((rank, ("OK", rates)))
    except Exception as e:  # noqa: BLE001
        q.put((rank, (f"FAIL: {type(e).__name__}: {e}", [])))


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--world", type=int, default=1, help="ranks (multi-process DP)")
    ap.add_argument("--batch-size", type=int, default=32, help="per-process batch")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--width-mult", type=float, default=1.0)
    ap.add_argument("--bf16", action="store_true", default=True)
    ap.add_argument("--no-bf16", dest="bf16", action="store_false")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--batches-per-iter", type=int, default=3)
    ap.add_argument("--cross-host", action="store_true",
                    help="add the DCN gradient tier (needs TPUNET_* env)")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse(argv)
    if args.world == 1:  # the world>1 parent never runs JAX
        from benchmarks import place_compile_cache

        place_compile_cache()
    if args.world > 1:
        from benchmarks import check_rank_results, spawn_ranks

        results = check_rank_results(spawn_ranks(
            _mp_worker, args.world, extra_args=(argv or sys.argv[1:],), timeout=3600
        ))
        per_rank = [results[r] for r in range(args.world)]
        totals = [sum(it) for it in zip(*per_rank)]
        mean, std = statistics.mean(totals), statistics.pstdev(totals)
        per = mean / args.world
        print(f"Img/sec per rank: {per:.1f}")
        print(f"Total img/sec on {args.world} rank(s): {mean:.1f} +-{1.96 * std:.1f}")
    else:
        rates = run_benchmark(args)
        mean, std = statistics.mean(rates), statistics.pstdev(rates)
        print(f"Img/sec: {mean:.1f} +-{1.96 * std:.1f}")


if __name__ == "__main__":
    main()
