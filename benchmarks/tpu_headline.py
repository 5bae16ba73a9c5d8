"""Single-chip model-tier headline: Transformer tokens/s + MFU, VGG16 img/s.

The reference's end-to-end validation was a real-hardware model benchmark
(reference README.md:52-84: VGG16 synthetic img/s on V100s); this module is
that tier for the TPU build, run by bench.py on the chip. MFU uses the
analytic transformer FLOP count (6N per token for the matmuls + 12*L*S*d
for attention scores/values, Chinchilla-appendix convention, embedding
lookup excluded) against the chip's peak bf16 FLOP/s by device kind.

Prints ONE JSON line:
  {"platform": "tpu", "device_kind": str, "device_count": N,
   "tokens_per_s": N, "mfu": N, "vgg_img_per_s": N}

There is no CPU tier: without a TPU the tool exits non-zero.
"""

from __future__ import annotations

import argparse
import json

# device_kind, exactly as `jax.devices()[0].device_kind` reports it, ->
# peak dense bf16 FLOP/s of one chip. Source: Google Cloud TPU documentation,
# the "System architecture" page of each generation ("TPU v4": 275 TFLOP/s;
# "TPU v5e": 197; "TPU v5p": 459; "TPU v6e": 918; "TPU v2": 45 and "TPU v3":
# 123 per chip, of which JAX shows each of the two cores as a device). A kind
# that is not in the table is an error, never a guess: a wrong peak makes
# every MFU wrong.
PEAK_FLOPS = {
    "TPU v2": 45e12 / 2,
    "TPU v3": 123e12 / 2,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def transformer_flops_per_token(n_params: int, vocab: int, d_model: int,
                                n_layers: int, seq: int) -> float:
    """Analytic train-step FLOPs per token: 6*N over the matmul params
    (embedding table excluded — a lookup, not a matmul; lm_head included)
    + attention 12*L*S*d_model (QK^T and PV, fwd+bwd). Chinchilla-appendix
    convention; shared with benchmarks.mfu_sweep so the sweep scores with
    exactly the headline's accounting."""
    n_matmul = n_params - vocab * d_model
    return 6 * n_matmul + 12 * n_layers * seq * d_model


def _peak_for(kind: str) -> float:
    """Peak bf16 FLOP/s for a device kind: an exact lookup."""
    try:
        return PEAK_FLOPS[kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s recorded for device kind {kind!r}; add it to "
            "benchmarks.tpu_headline.PEAK_FLOPS with its source") from None


def transformer_bench() -> tuple[float, float]:
    """Returns (tokens_per_s, mfu): bf16, flash attention, remat, at the
    shape sized to one v5e-class chip (benchmarks.mfu_sweep, PERF_NOTES.md):
    ~735M params + f32 adamw fills most of HBM under donation. The swept
    alternatives — batch 16, L16 and d4096 (both OOM) — lost."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import chained_step_time
    from tpunet.models import Transformer
    from tpunet.train import create_train_state, make_train_step

    cfg = dict(vocab=32000, d_model=2048, n_layers=12, n_heads=16, d_ff=8192)
    batch, seq = 8, 2048
    model = Transformer(compute_dtype=jnp.bfloat16, attn_impl="flash",
                        remat=True, **cfg)
    tx = optax.adamw(3e-4)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab"], (batch, seq)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    state, _ = create_train_state(model, jax.random.PRNGKey(0), tokens, tx)
    # donate=True is the real-training memory profile — without it the chip
    # must hold two optimizer states and the chip-sized config OOMs.
    step = make_train_step(model, tx)

    dt = chained_step_time(step, state, (tokens, labels, jax.random.PRNGKey(1)),
                           warmup=2, iters=8)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    flops_per_token = transformer_flops_per_token(
        n_params, cfg["vocab"], cfg["d_model"], cfg["n_layers"], seq)
    peak = _peak_for(jax.devices()[0].device_kind)
    return batch * seq / dt, flops_per_token * batch * seq / dt / peak


def vgg_bench() -> float:
    """VGG16 synthetic img/s — the reference's own end-to-end workload."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import chained_step_time
    from tpunet.models import vgg16
    from tpunet.train import create_train_state, make_train_step, synthetic_batch

    batch = 64
    model = vgg16(num_classes=1000)
    tx = optax.sgd(1e-2, momentum=0.9)
    images, labels = synthetic_batch(np.random.default_rng(0), batch, 224, 1000)
    images, labels = jnp.asarray(images), jnp.asarray(labels)
    state, _ = create_train_state(model, jax.random.PRNGKey(0), images, tx)
    step = make_train_step(model, tx)
    dt = chained_step_time(step, state, (images, labels, jax.random.PRNGKey(1)),
                           warmup=2, iters=8)
    return batch / dt


def main(argv=None) -> None:
    argparse.ArgumentParser(description=__doc__).parse_args(argv)

    from benchmarks import claim_device

    dev = claim_device()
    tokens_per_s, mfu = transformer_bench()
    img_per_s = vgg_bench()
    print(json.dumps({
        **dev,
        "attn": "flash",
        "tokens_per_s": round(tokens_per_s, 1),
        "mfu": round(mfu, 4),
        "vgg_img_per_s": round(img_per_s, 2),
    }))


if __name__ == "__main__":
    main()
