"""Per-segment MFU attribution for the headline train step.

The round-3 headline (MFU 0.411 on v5e) left ~59% of the chip unexplained
— nothing in the repo could say where a step's time goes. This tool times
each segment of the headline step IN ISOLATION with the same chained-
timing methodology the headline uses (sync once at the end of a K-step
dependency chain, benchmarks.chained_step_time), then reconciles the sum
against the measured full step:

  expected_full = L*(attn + qkvo + ffn)[fwd+bwd]           (the blocks)
                + L*(attn + qkvo + ffn)[fwd]               (remat recompute)
                + xent[fwd+bwd] + adamw                    (head + optimizer)
  residual      = measured_full - expected_full            (LN, elementwise,
                                                            embed, dispatch)

Each segment also gets an analytic FLOP count (same 6N/12LSd convention as
benchmarks.tpu_headline, so shares line up with the headline MFU) and a
per-segment efficiency = FLOPs / time / peak — the column that says which
segment to tune. Segment chaining perturbs inputs by the carry scalar and
consumes grads with a tree-sum; both add O(bytes) elementwise work
(~5-10% overhead at headline shapes), so treat per-segment efficiencies as
slightly pessimistic, and the residual as slightly optimistic.

--sweep-blocks instead times the attention segment alone over a grid of
flash (block_q, block_k) at the given seq — the tool for picking kernel
block sizes at s2048 vs s8192 (verdict round 3 item 4).

Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import time


def _chained_time(fn, carry0, warmup: int, iters: int) -> float:
    """Per-call seconds for carry -> carry scalar chains, synced once."""
    carry = carry0
    for _ in range(max(warmup, 1)):
        carry = fn(carry)
    if not math.isfinite(float(carry)):
        raise RuntimeError("non-finite carry in warmup")
    t0 = time.perf_counter()
    for _ in range(iters):
        carry = fn(carry)
    final = float(carry)  # the one chain-wide sync
    dt = (time.perf_counter() - t0) / iters
    if not math.isfinite(final):
        raise RuntimeError("non-finite carry in timing chain")
    return dt


def _tree_sum(tree):
    import jax
    import jax.numpy as jnp

    return sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(tree))


def segments(cfg: dict, *, block_q: int | None = None,
             block_k: int | None = None):
    """Build {name: (chained_fn, carry0, flops_fwd, flops_fwdbwd)} for one
    layer's blocks plus the model-level head/optimizer segments.

    FLOP convention matches tpu_headline.transformer_flops_per_token: 2*m*n*k
    per matmul forward, bwd = 2x fwd, attention 4*B*S^2*d fwd (no causal
    discount). adamw gets flops=0 — it is HBM-bound; its line is time-only.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from tpunet.ops.flash_attention import flash_attention

    B, S, d, ff, H, V = (cfg["batch"], cfg["seq"], cfg["d_model"],
                         cfg["d_ff"], cfg["n_heads"], cfg["vocab"])
    dh = d // H
    key = jax.random.PRNGKey(0)
    dtype = jnp.bfloat16 if cfg["bf16"] else jnp.float32
    x0 = jax.random.normal(key, (B * S, d), dtype)
    qkv0 = jax.random.normal(key, (B, S, H, dh), dtype)
    out: dict = {}

    use_flash = cfg["bf16"]  # flash needs tile shapes; CPU smoke uses ref

    def attn_fwd(c):
        q = qkv0 * (1 + c * 1e-6)
        if use_flash:
            o = flash_attention(q, q, q, True, block_q=block_q,
                                block_k=block_k)
        else:
            from tpunet.ops.flash_attention import attention_reference

            o = attention_reference(q, q, q, True)
        return jnp.sum(o.astype(jnp.float32)) * 1e-9

    def attn_fwdbwd(c):
        def loss(q):
            if use_flash:
                o = flash_attention(q, q, q, True, block_q=block_q,
                                    block_k=block_k)
            else:
                from tpunet.ops.flash_attention import attention_reference

                o = attention_reference(q, q, q, True)
            return jnp.sum(o.astype(jnp.float32))

        v, g = jax.value_and_grad(loss)(qkv0 * (1 + c * 1e-6))
        return (v + _tree_sum(g)) * 1e-9

    a_fwd = 4 * B * S * S * d  # QK^T + PV, 2*B*H*S*S*dh each
    out["attn"] = (attn_fwd, attn_fwdbwd, a_fwd, 3 * a_fwd)

    w_qkvo = [jax.random.normal(jax.random.PRNGKey(i + 1), (d, d), dtype) * 0.02
              for i in range(4)]

    def qkvo_fwd(c):
        x = x0 * (1 + c * 1e-6)
        acc = 0.0
        for w in w_qkvo:
            acc = acc + jnp.sum((x @ w).astype(jnp.float32))
        return acc * 1e-9

    def qkvo_fwdbwd(c):
        def loss(x, ws):
            return sum(jnp.sum((x @ w).astype(jnp.float32)) for w in ws)

        v, g = jax.value_and_grad(loss, argnums=(0, 1))(x0 * (1 + c * 1e-6),
                                                        w_qkvo)
        return (v + _tree_sum(g)) * 1e-9

    p_fwd = 2 * B * S * 4 * d * d
    out["qkvo"] = (qkvo_fwd, qkvo_fwdbwd, p_fwd, 3 * p_fwd)

    w_up = jax.random.normal(jax.random.PRNGKey(11), (d, ff), dtype) * 0.02
    w_dn = jax.random.normal(jax.random.PRNGKey(12), (ff, d), dtype) * 0.02

    def ffn_fwd(c):
        x = x0 * (1 + c * 1e-6)
        return jnp.sum((jax.nn.gelu(x @ w_up) @ w_dn).astype(jnp.float32)) * 1e-9

    def ffn_fwdbwd(c):
        def loss(x, wu, wd):
            return jnp.sum((jax.nn.gelu(x @ wu) @ wd).astype(jnp.float32))

        v, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(
            x0 * (1 + c * 1e-6), w_up, w_dn)
        return (v + _tree_sum(g)) * 1e-9

    f_fwd = 2 * B * S * 2 * d * ff
    out["ffn"] = (ffn_fwd, ffn_fwdbwd, f_fwd, 3 * f_fwd)

    w_head = jax.random.normal(jax.random.PRNGKey(13), (d, V), dtype) * 0.02
    labels0 = jax.random.randint(jax.random.PRNGKey(14), (B * S,), 0, V)

    def xent_fwdbwd(c):
        def loss(x, w):
            logits = (x @ w).astype(jnp.float32)
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits, labels0))

        v, g = jax.value_and_grad(loss, argnums=(0, 1))(x0 * (1 + c * 1e-6),
                                                        w_head)
        return v + _tree_sum(g) * 1e-9

    x_fwd = 2 * B * S * d * V
    out["xent"] = (None, xent_fwdbwd, x_fwd, 3 * x_fwd)
    return out


def _adamw_segment(n_params_target: int, warmup: int, iters: int) -> float:
    """Time an adamw update on a f32 param tree of ~n_params_target,
    chained through (params, opt_state). HBM-bound: p+m+v+g traffic."""
    import jax
    import jax.numpy as jnp
    import optax

    # A few big leaves, like a real model (per-leaf overhead is negligible
    # either way at headline scale).
    n_leaf = max(n_params_target // 8, 1)
    params = [jax.random.normal(jax.random.PRNGKey(i), (n_leaf,), jnp.float32)
              for i in range(8)]
    grads = [jnp.full((n_leaf,), 1e-4, jnp.float32) for _ in range(8)]
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    @jax.jit
    def upd(params, opt_state):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def fn(carry):
        p, s = carry
        return upd(p, s)

    carry = (params, opt_state)
    for _ in range(max(warmup, 1)):
        carry = fn(carry)
    float(jnp.sum(carry[0][0][:1]))  # sync warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        carry = fn(carry)
    float(jnp.sum(carry[0][0][:1]))  # chain-wide sync (depends on all steps)
    return (time.perf_counter() - t0) / iters


def run_attribution(cfg: dict, warmup: int, iters: int, dev: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks import chained_step_time
    from benchmarks.tpu_headline import _peak_for, transformer_flops_per_token
    from tpunet.models import Transformer
    from tpunet.train import create_train_state, make_train_step

    peak = (_peak_for(dev["device_kind"]) if dev["platform"] == "tpu"
            else None)
    L = cfg["n_layers"]

    segs = segments(cfg)
    rows: dict[str, dict] = {}
    for name, (fwd, fwdbwd, fl_fwd, fl_fwdbwd) in segs.items():
        jitted_b = jax.jit(fwdbwd)
        t_b = _chained_time(jitted_b, jnp.float32(0), warmup, iters)
        row = {"fwdbwd_ms": round(t_b * 1e3, 3),
               "eff_fwdbwd": round(fl_fwdbwd / t_b / peak, 3) if peak else None}
        if fwd is not None:
            t_f = _chained_time(jax.jit(fwd), jnp.float32(0), warmup, iters)
            row["fwd_ms"] = round(t_f * 1e3, 3)
            row["eff_fwd"] = round(fl_fwd / t_f / peak, 3) if peak else None
        rows[name] = row

    # Optimizer on the real parameter count.
    model = Transformer(
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_layers=L,
        n_heads=cfg["n_heads"], d_ff=cfg["d_ff"],
        compute_dtype=jnp.bfloat16 if cfg["bf16"] else jnp.float32,
        attn_impl="flash" if cfg["bf16"] else "reference", remat=cfg["bf16"])
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab"],
                                      (cfg["batch"], cfg["seq"])), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    tx = optax.adamw(3e-4)
    state, _ = create_train_state(model, jax.random.PRNGKey(0), tokens, tx)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    rows["adamw"] = {"fwdbwd_ms": round(
        _adamw_segment(n_params, warmup, iters) * 1e3, 3)}

    # The measured full step, same harness as the headline.
    step = make_train_step(model, tx)
    t_full = chained_step_time(
        step, state, (tokens, labels, jax.random.PRNGKey(1)),
        warmup=warmup, iters=iters)

    blocks_fwdbwd = sum(rows[n]["fwdbwd_ms"] for n in ("attn", "qkvo", "ffn"))
    blocks_fwd = sum(rows[n]["fwd_ms"] for n in ("attn", "qkvo", "ffn"))
    expected = (L * (blocks_fwdbwd + (blocks_fwd if cfg["bf16"] else 0))
                + rows["xent"]["fwdbwd_ms"] + rows["adamw"]["fwdbwd_ms"])
    flops_step = transformer_flops_per_token(
        n_params, cfg["vocab"], cfg["d_model"], L, cfg["seq"]
    ) * cfg["batch"] * cfg["seq"]
    return {
        **dev,
        "config": {k: cfg[k] for k in ("d_model", "n_layers", "d_ff",
                                       "n_heads", "batch", "seq")},
        "n_params": n_params,
        "segments": rows,
        "full_step_ms": round(t_full * 1e3, 3),
        "mfu": round(flops_step / t_full / peak, 4) if peak else None,
        # remat=True re-runs each block's forward during bwd; the expected
        # model includes that extra fwd pass per layer.
        "expected_full_ms": round(expected, 3),
        "residual_ms": round(t_full * 1e3 - expected, 3),
        "note": "segments timed in isolation (chained, one sync); "
                "residual = LN + elementwise + embed + dispatch + "
                "model-vs-segment discrepancies",
    }


def run_block_sweep(cfg: dict, blocks: list[int], warmup: int,
                    iters: int, dev: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.tpu_headline import _peak_for

    peak = (_peak_for(dev["device_kind"]) if dev["platform"] == "tpu"
            else None)
    a_fwdbwd = 12 * cfg["batch"] * cfg["seq"] * cfg["seq"] * cfg["d_model"]
    grid: dict[str, dict] = {}
    for bq in blocks:
        for bk in blocks:
            if bq > cfg["seq"] or bk > cfg["seq"]:
                continue
            # Untileable pairs silently fall back to the reference einsum
            # inside flash_attention, and compiled Mosaic silently clamps
            # non-lane-aligned blocks (_normalize_blocks) — timing either
            # would crown a fake "best". Both rules the model-level knob
            # enforces (transformer.py SelfAttention validation).
            if cfg["seq"] % bq or cfg["seq"] % bk or bq % bk:
                grid[f"bq{bq}_bk{bk}"] = {"skipped": "untileable (causal)"}
                continue
            min_sublane = 16 if cfg["bf16"] else 8
            if ((bq % 128 and bq != cfg["seq"])
                    or (bk % min_sublane and bk != cfg["seq"])):
                grid[f"bq{bq}_bk{bk}"] = {
                    "skipped": "not Mosaic-legal (would be clamped)"}
                continue
            segs = segments(cfg, block_q=bq, block_k=bk)
            _, fwdbwd, _, _ = segs["attn"]
            try:
                t = _chained_time(jax.jit(fwdbwd), jnp.float32(0),
                                  warmup, iters)
                grid[f"bq{bq}_bk{bk}"] = {
                    "fwdbwd_ms": round(t * 1e3, 3),
                    "eff": round(a_fwdbwd / t / peak, 3) if peak else None}
            except Exception as e:  # noqa: BLE001 — a Mosaic reject is data
                grid[f"bq{bq}_bk{bk}"] = {
                    "error": f"{type(e).__name__}: {str(e).splitlines()[0][:200]}"}
    ok = {k: v["fwdbwd_ms"] for k, v in grid.items() if "fwdbwd_ms" in v}
    return {
        **dev,
        "seq": cfg["seq"], "batch": cfg["batch"], "d_model": cfg["d_model"],
        "grid": grid,
        "best": min(ok, key=ok.get) if ok else None,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu",
                    help="needs a TPU; cpu (with --fp32) tests the tool")
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--ff", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--fp32", action="store_true",
                    help="CPU smoke mode: f32 + reference attention")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--sweep-blocks", action="store_true",
                    help="time the attention segment over a flash "
                         "(block_q, block_k) grid instead")
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=[128, 256, 512])
    args = ap.parse_args(argv)

    from benchmarks import claim_device

    dev = claim_device(args.platform)
    cfg = dict(d_model=args.d, n_layers=args.layers, d_ff=args.ff,
               n_heads=args.heads, vocab=args.vocab, batch=args.batch,
               seq=args.seq, bf16=not args.fp32)
    if args.sweep_blocks:
        print(json.dumps(run_block_sweep(cfg, args.blocks, args.warmup,
                                         args.iters, dev)))
    else:
        print(json.dumps(run_attribution(cfg, args.warmup, args.iters, dev)))


if __name__ == "__main__":
    main()
