"""Transformer synthetic training benchmark (tokens/s).

The long-context companion to `benchmarks.vgg_synthetic`: times the jitted
Transformer train step (fwd+bwd+update) on synthetic token batches and
reports tokens/s mean ± std. Exercises the parallelism axes end-to-end:

  Single process: dp×sp×mdl mesh over local devices — ring attention over
  `sp` (context length scales with devices), Megatron TP over `mdl`.
      python -m benchmarks.lm_synthetic --seq 2048 --sp 2 --tp 2
  Multi-process (-n N): per-rank local step + cross-host DCN gradient tier
  (ring allreduce over the multi-stream transport).
      python -m benchmarks.lm_synthetic -n 2 --layers 2 --d-model 128
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
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpunet.models import Transformer, transformer_partition_rules
    from tpunet.parallel import make_named_mesh, replicated, shard_params
    from tpunet.train import (TrainState, create_train_state,
                              create_zero_train_state, make_train_step,
                              make_zero_train_step)

    use_mesh = args.sp > 1 or args.tp > 1
    mesh = None
    if use_mesh:
        n = len(jax.devices())
        dp = max(1, n // (args.sp * args.tp))
        mesh = make_named_mesh({"dp": dp, "sp": args.sp, "mdl": args.tp})

    model = Transformer(
        vocab=args.vocab, d_model=args.d_model, n_layers=args.layers,
        n_heads=args.heads, d_ff=4 * args.d_model, n_experts=args.experts,
        moe_top_k=args.moe_top_k, capacity_factor=args.capacity_factor,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        attn_impl=("zigzag" if args.zigzag else "ring") if args.sp > 1
        else "reference",
        mesh=mesh, tp_axis="mdl" if args.tp > 1 else None,
    )
    tx = optax.adamw(3e-4)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, args.vocab, size=(args.batch_size, args.seq))
    tokens = jnp.asarray(toks, jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    if args.zigzag:
        from tpunet.parallel import to_zigzag

        # The whole pipeline runs in zigzag sequence order; labels are
        # next-token in NATURAL order, permuted the same way.
        tokens = to_zigzag(tokens, args.sp)
        labels = to_zigzag(labels, args.sp)
    if args.zero:
        if not args.cross_host:
            raise SystemExit("--zero requires --cross-host (it shards the "
                             "optimizer over the DCN world)")
        if args.bucket_bytes is not None:
            raise SystemExit("--bucket-bytes applies to the all-reduce path; "
                             "the ZeRO path syncs via reduce-scatter/all-gather "
                             "(refusing to silently benchmark the wrong path)")
        state, _ = create_zero_train_state(model, jax.random.PRNGKey(0), tokens, tx)
    else:
        state, _ = create_train_state(model, jax.random.PRNGKey(0), tokens, tx)

    if mesh is not None:
        rules = transformer_partition_rules(
            tp_axis="mdl" if args.tp > 1 else None, ep_axis=None
        )
        params = jax.device_put(state.params, shard_params(state.params, mesh, rules))
        opt_state = jax.tree.map(
            lambda leaf: jax.device_put(leaf, replicated(mesh)), state.opt_state
        )
        state = TrainState(params, opt_state, jax.device_put(state.step, replicated(mesh)))
        data_sh = NamedSharding(mesh, P("dp", "sp"))
        tokens = jax.device_put(tokens, data_sh)
        labels = jax.device_put(labels, data_sh)

    if args.zero:
        step = make_zero_train_step(model, tx, donate=True,
                                    fused_xent_block=args.fused_xent,
                                    accum_steps=args.accum)
    else:
        # Passed through unguarded: make_train_step rejects bucket_bytes
        # without cross_host, which is better than silently benchmarking the
        # wrong path.
        step = make_train_step(model, tx, cross_host=args.cross_host, donate=True,
                               bucket_bytes=args.bucket_bytes,
                               fused_xent_block=args.fused_xent,
                               accum_steps=args.accum)
    return state, step, tokens, labels, mesh


def run_benchmark(args, emit=print):
    import contextlib

    import jax

    state, step, tokens, labels, mesh = _build(args)
    rngkey = jax.random.PRNGKey(1)
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        loss = None
        for _ in range(args.warmup):
            state, loss = step(state, tokens, labels, rngkey)
        if loss is not None:
            loss.block_until_ready()
        rates = []
        tokens_per_batch = args.batch_size * args.seq
        for it in range(args.iters):
            t0 = time.perf_counter()
            for _ in range(args.batches_per_iter):
                state, loss = step(state, tokens, labels, rngkey)
            loss.block_until_ready()
            dt = time.perf_counter() - t0
            rates.append(tokens_per_batch * args.batches_per_iter / dt)
            emit(f"Iter #{it}: {rates[-1]:.0f} tokens/sec")
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
    ap.add_argument("-n", "--world", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=8, help="per-process")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--experts", type=int, default=0)
    ap.add_argument("--moe-top-k", type=int, default=1,
                    help="experts per token (2 = GShard/Mixtral routing); "
                         "the model already scales expert capacity by k")
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--sp", type=int, default=1, help="sequence-parallel axis size")
    ap.add_argument("--zigzag", action="store_true",
                    help="balanced causal context parallelism (zigzag layout) "
                         "instead of the contiguous ring; requires --sp > 1")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel axis size")
    ap.add_argument("--bf16", action="store_true", default=True)
    ap.add_argument("--no-bf16", dest="bf16", action="store_false")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--batches-per-iter", type=int, default=3)
    ap.add_argument("--cross-host", action="store_true")
    ap.add_argument("--accum", type=int, default=None, metavar="K",
                    help="gradient accumulation over K microbatches (batch "
                         "size must divide by K)")
    ap.add_argument("--fused-xent", type=int, default=None, metavar="BLOCK",
                    help="blockwise fused cross-entropy with this vocab block "
                         "size (never materializes the full logits tensor)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-1: shard optimizer state over the DCN world "
                         "(reduce-scatter grads, all-gather params)")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="multi-rank only: nonblocking bucketed gradient sync "
                         "(overlaps DCN transfer with backward); bytes per bucket")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse(argv)
    need = args.sp * args.tp
    if args.zigzag and args.sp <= 1:
        # Validated BEFORE any worker spawns: a SystemExit inside a spawned
        # worker escapes its `except Exception` reporter and would leave the
        # parent blocking on the result queue instead of printing this.
        raise SystemExit("--zigzag requires --sp > 1 (it is the balanced "
                         "causal layout for sequence parallelism)")
    if args.world > 1 and need > 1:
        # Loopback ranks are single-device; silently downgrading sp/tp would
        # report tokens/s for a configuration the user didn't ask for.
        raise SystemExit(
            "--sp/--tp (in-process mesh axes) apply to single-process mode; "
            "with -n, each rank is one device and parallelism is cross-host DP"
        )
    flags = os.environ.get("XLA_FLAGS", "")
    if (os.environ.get("JAX_PLATFORMS") == "cpu" and need > 1
            and "--xla_force_host_platform_device_count" not in flags):
        # CPU smoke runs of the sp/tp mesh need virtual devices, and the
        # flag must be set before the first jax import.
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={max(8, need)}".strip()
        )
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
        print(f"Tokens/sec per rank: {mean / args.world:.0f}")
        print(f"Total tokens/sec on {args.world} rank(s): {mean:.0f} +-{1.96 * std:.0f}")
    else:
        rates = run_benchmark(args)
        mean, std = statistics.mean(rates), statistics.pstdev(rates)
        print(f"Tokens/sec: {mean:.0f} +-{1.96 * std:.0f}")


if __name__ == "__main__":
    main()
