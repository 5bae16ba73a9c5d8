"""Per-kernel compile+run smoke on the chip, run by bench.py before the model
tier: each Pallas kernel variant is compiled and run on a tiny input and
compared with the reference einsum, so a Mosaic rejection or a wrong result
is named per kernel instead of surfacing as a failed model step.

Prints ONE JSON line: {"flash_fwd": "ok"|"<error>", "flash_bwd": ...,
"platform", "device_kind", "device_count"} and exits non-zero unless every
kernel entry is "ok".
"""

from __future__ import annotations

import argparse
import json

KERNELS = ("flash_fwd", "flash_bwd", "flash_gqa_fwd", "flash_gqa_bwd",
           "flash_window_fwd", "flash_window_bwd",
           "flash_gqa_window_fwd", "flash_gqa_window_bwd")


def _short(e: Exception) -> str:
    return f"{type(e).__name__}: {str(e).splitlines()[0][:300]}"


def _parity(a, b) -> float:
    """Max error relative to the reference's scale — an absolute threshold
    misfires when the compared quantity's magnitude varies (e.g. GQA
    gradients sum a whole group of heads)."""
    import jax.numpy as jnp

    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a32 - b32)) / jnp.maximum(jnp.max(jnp.abs(b32)), 1.0))


def run_smoke() -> dict:
    """{kernel: "ok" | error text} for every name in KERNELS. Runs wherever
    JAX runs (the Pallas interpreter off the chip, which the CPU tests use);
    main() is what insists on the chip."""
    import jax
    import jax.numpy as jnp

    from tpunet.ops.flash_attention import attention_reference, flash_attention

    # Small but tile-shaped: block-sized seq, MXU-width head_dim, bf16 like
    # the headline config (dtype changes the Mosaic tiling rules).
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 4, 128), jnp.bfloat16)
    # GQA: the kv BlockSpec index_maps (bh // group) and the group-wide dK/dV
    # blocks are distinct Mosaic programs from the MHA case.
    kv = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 128), jnp.bfloat16)

    def rep(x):
        return jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)

    out: dict = {}
    # Sliding window: the k-block loop gains a LOWER bound in fwd and an
    # UPPER bound in the dK/dV pass — new Mosaic programs reachable from the
    # public model API (attn_window=). window=192 with S=256, bk=128
    # exercises both a fully-inside and a partially-masked k-block on each
    # side of the boundary. GQA x window compose in one kernel, a combination
    # Mosaic could reject even when each passes alone.
    for gqa in (False, True):
        for window in (None, 192):
            name = ("flash" + ("_gqa" if gqa else "")
                    + ("_window" if window else ""))
            # MHA differentiates through q, k and v at once (dQ and dK/dV
            # kernels); GQA through the narrower k/v against a fixed q.
            x = kv if gqa else q

            def flash(x, gqa=gqa, window=window):
                return flash_attention(q if gqa else x, x, x, True,
                                       window=window)

            def ref(x, gqa=gqa, window=window):
                return attention_reference(q if gqa else x, rep(x), rep(x),
                                           True, window=window)

            # Thresholds: bwd allows 6% relative (bf16 grads accumulate ~1%
            # ulp noise over S=256 sums; a wrong kernel is O(1) off), fwd 2%.
            for suffix, tol, wrap in (
                    ("_fwd", 0.02, lambda f: f),
                    ("_bwd", 0.06,
                     lambda f: jax.grad(lambda x: jnp.sum(f(x))))):
                try:
                    err = _parity(jax.jit(wrap(flash))(x),
                                  jax.jit(wrap(ref))(x))
                    out[name + suffix] = ("ok" if err < tol
                                          else f"parity {err:.3e}")
                except Exception as e:  # noqa: BLE001 — reported per kernel; main() exits non-zero
                    out[name + suffix] = _short(e)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu",
                    help="cpu runs the kernels in the Pallas interpreter, to "
                         "test this tool; it says nothing about Mosaic")
    args = ap.parse_args(argv)

    from benchmarks import claim_device

    dev = claim_device(args.platform)
    out = run_smoke()
    print(json.dumps({**out, **dev}))
    failed = {k: out.get(k, "missing") for k in KERNELS if out.get(k) != "ok"}
    if failed:
        raise SystemExit(f"kernel smoke failed: {failed}")


if __name__ == "__main__":
    main()
