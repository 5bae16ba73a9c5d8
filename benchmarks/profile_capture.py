"""Capture a jax.profiler trace of the headline train step (TPU or CPU).

The chained-timing tools (benchmarks.mfu_attribution) attribute step time
by re-timing isolated segments; a profiler trace is the ground-truth
cross-check — per-op device timelines straight from the runtime. This
wraps the headline step in `jax.profiler.trace` for a few post-warmup
steps and reports where the trace landed (point perfetto/tensorboard at
it). A run of its own: tracing slows the host, so end-to-end numbers are
taken with the profiler off.

Usage: python -m benchmarks.profile_capture [--out DIR] [--steps 3]
       [--platform cpu] [--d ... --layers ... etc like mfu_attribution]
Needs a TPU; `--platform cpu` tests the tool at a smoke shape.
Prints ONE JSON line: {"platform", "device_kind", "device_count",
"trace_dir": ..., "files": N, "step_ms": ...}.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="/tmp/tpunet_trace")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu")
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--ff", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args(argv)
    if args.steps < 1:
        raise SystemExit(f"--steps must be >= 1, got {args.steps}")

    from benchmarks import claim_device

    dev = claim_device(args.platform)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpunet.models import Transformer
    from tpunet.train import create_train_state, make_train_step

    on_tpu = dev["platform"] == "tpu"
    if not on_tpu:  # CPU smoke shape — the tool contract, not the numbers
        args.d, args.layers, args.ff, args.heads = 64, 2, 128, 4
        args.vocab, args.batch, args.seq = 512, 2, 128

    model = Transformer(
        vocab=args.vocab, d_model=args.d, n_layers=args.layers,
        n_heads=args.heads, d_ff=args.ff,
        compute_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        attn_impl="flash" if on_tpu else "reference", remat=on_tpu)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, args.vocab, (args.batch, args.seq)),
                         jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    tx = optax.adamw(3e-4)
    state, _ = create_train_state(model, jax.random.PRNGKey(0), tokens, tx)
    step = make_train_step(model, tx)

    # Warmup/compile OUTSIDE the trace (a trace dominated by compilation is
    # useless for per-op attribution).
    for _ in range(2):
        state, loss = step(state, tokens, labels, jax.random.PRNGKey(1))
    float(loss)  # sync

    os.makedirs(args.out, exist_ok=True)
    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for _ in range(args.steps):
            state, loss = step(state, tokens, labels, jax.random.PRNGKey(1))
        final = float(loss)  # chain-wide sync inside the trace window
    dt = (time.perf_counter() - t0) / args.steps
    if final != final:  # NaN
        raise SystemExit("non-finite loss during trace")
    files = glob.glob(os.path.join(args.out, "**", "*"), recursive=True)
    print(json.dumps({
        **dev,
        "trace_dir": args.out,
        "files": len([f for f in files if os.path.isfile(f)]),
        "step_ms": round(dt * 1e3, 2),
        "note": "open with tensorboard --logdir or perfetto; step_ms is "
                "trace-window wall (chained, one sync)",
    }))


if __name__ == "__main__":
    main()
