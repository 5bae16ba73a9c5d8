"""Chip-sizing sweep: chained-timing MFU per transformer config on the
local accelerator. This is the tool that sized `tpu_headline`'s TPU config
(round-3 numbers recorded in PERF_NOTES.md): run it when the bench hardware
changes to re-pick the headline shape.

Usage: python -m benchmarks.mfu_sweep [config indices...]
Needs a TPU. Prints one JSON line per config: device, params, step time,
tokens/s, TFLOP/s, MFU (against the device's peak bf16 FLOP/s).
"""

from __future__ import annotations

import json
import sys

CONFIGS = [
    # (d_model, layers, d_ff, heads, batch, seq, remat[, remat_policy])
    (2048, 12, 8192, 16, 8, 2048, True),   # the round-3 v5e headline winner
    (2048, 12, 8192, 16, 16, 2048, True),
    (2048, 16, 8192, 16, 8, 2048, True),   # OOM on 16 GB v5e
    (4096, 4, 16384, 32, 8, 2048, True),   # OOM on 16 GB v5e
    (1024, 12, 4096, 16, 16, 2048, True),  # half-size, for smaller chips
    # Long-context: flash O(S) memory is what makes s8192 fit at all —
    # reference attention would materialize b*h*S^2 scores (>8 GB here).
    (2048, 12, 8192, 16, 2, 8192, True),
    # Selective remat: full-block remat re-executes the forward (~8ND run vs
    # 6ND counted -> MFU ceiling 0.75); "dots" saves matmul outputs and
    # recomputes only elementwise, trading HBM back for recompute FLOPs.
    (2048, 12, 8192, 16, 8, 2048, True, "dots"),
    (2048, 12, 8192, 16, 8, 2048, False),  # no remat at all (OOM probe)
    (2048, 12, 8192, 16, 4, 2048, True, "dots"),  # dots at half batch
]

# Fused blockwise cross-entropy (tpunet.ops.blockwise_cross_entropy) per
# config index: skips materializing the (b*s, 32000) logits. Applied to the
# long-context config where that tensor is the limiting resident.
FUSED_XENT = {5: 8192}


def main(argv=None) -> None:
    from benchmarks import claim_device

    dev = claim_device()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import chained_step_time
    from benchmarks.tpu_headline import _peak_for, transformer_flops_per_token
    from tpunet.models import Transformer
    from tpunet.train import create_train_state, make_train_step

    args = argv if argv is not None else sys.argv[1:]
    which = [int(x) for x in args] or list(range(len(CONFIGS)))
    peak = _peak_for(dev["device_kind"])

    for ci in which:
        d, n_layers, ff, heads, batch, seq, remat, *rest = CONFIGS[ci]
        policy = rest[0] if rest else None
        cfg = dict(vocab=32000, d_model=d, n_layers=n_layers, n_heads=heads, d_ff=ff)
        model = Transformer(compute_dtype=jnp.bfloat16, attn_impl="flash",
                            remat=remat, remat_policy=policy, **cfg)
        tx = optax.adamw(3e-4)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg["vocab"], (batch, seq)), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        try:
            state, _ = create_train_state(model, jax.random.PRNGKey(0), tokens, tx)
            n_params = sum(x.size for x in jax.tree.leaves(state.params))
            step = make_train_step(model, tx,  # donated: real-training memory
                                   fused_xent_block=FUSED_XENT.get(ci))
            dt = chained_step_time(step, state,
                                   (tokens, labels, jax.random.PRNGKey(1)),
                                   warmup=1, iters=8)
        except Exception as e:  # noqa: BLE001 — a config OOMing is a result
            print(json.dumps({**dev, "cfg": ci, "error": str(e)[:200]}),
                  flush=True)
            continue
        fpt = transformer_flops_per_token(n_params, cfg["vocab"], d, n_layers, seq)
        fps = fpt * batch * seq
        print(json.dumps({
            **dev, "cfg": ci, "d": d, "L": n_layers, "ff": ff, "b": batch, "s": seq,
            **({"remat_policy": policy} if policy else {}),
            **({} if remat else {"remat": False}),
            "params_M": round(n_params / 1e6, 1),
            "step_s": round(dt, 4),
            "tok_s": round(batch * seq / dt, 1),
            "tflops": round(fps / dt / 1e12, 1),
            "mfu": round(fps / dt / peak, 4),
        }), flush=True)


if __name__ == "__main__":
    main()
