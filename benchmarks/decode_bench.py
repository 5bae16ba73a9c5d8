"""Autoregressive decode throughput: tokens/s out of `tpunet.models.generate`.

The training headline (`tpu_headline`) measures MXU-bound step throughput;
this measures the inference regime the KV cache exists for — one token per
step, attention against the cached prefix, batch as the only MXU feeder.
GQA directly scales this bench: the KV cache (the HBM resident that limits
batch) shrinks by n_heads/n_kv_heads.

The whole generate() call — prefill + lax.scan decode — is wrapped in ONE
jit, so the timed region is a single executable; the clock stops on the
transfer of the token matrix to the host, which waits for the device.

Usage: python -m benchmarks.decode_bench [--platform cpu] [--kv-heads K]
Needs a TPU; `--platform cpu` is for testing the tool at a toy size.
Prints one JSON line: device, config, prefill+decode wall, decode tokens/s.
"""

from __future__ import annotations

import argparse
import json
import time


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default="tpu", choices=["cpu", "tpu"])
    p.add_argument("--d", type=int, default=1024)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--ff", type=int, default=4096)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--new", type=int, default=128)
    p.add_argument("--kv-heads", type=int, default=None,
                   help="grouped-query kv heads (default: = heads)")
    p.add_argument("--window", type=int, default=None,
                   help="sliding-window attention span (default: full causal)")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--spec-gamma", type=int, default=None,
                   help="also bench speculative decoding with this draft "
                        "block length")
    p.add_argument("--draft-layers", type=int, default=2,
                   help="draft model depth for --spec-gamma shallow mode "
                        "(same d/heads/vocab; random weights)")
    p.add_argument("--spec-per-row", action="store_true",
                   help="per-row speculative commits (each row keeps its "
                        "own accepted prefix; lockstep min otherwise)")
    p.add_argument("--spec-draft", choices=["shallow", "quant"],
                   default="shallow",
                   help="shallow = random small draft (acceptance floor + "
                        "analytic ceiling); quant = the target itself, "
                        "int8-quantized (a REAL draft: high acceptance, "
                        "honest end-to-end tokens/s)")
    p.add_argument("--quant", choices=["int8"], default=None,
                   help="also bench the int8 weight-only model's decode "
                        "tokens/s (halved weight HBM traffic)")
    p.add_argument("--attn", choices=["reference", "flash"],
                   default="reference",
                   help="attention impl: decode steps always use the cached "
                        "dense path, but the EMPTY-CACHE prefill routes "
                        "through this kernel — flash makes time-to-first-"
                        "token O(p) memory and MXU-tiled")
    args = p.parse_args(argv)

    from benchmarks import claim_device

    dev = claim_device(args.platform)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpunet.models import Transformer, generate

    model = Transformer(
        vocab=args.vocab, d_model=args.d, n_layers=args.layers,
        n_heads=args.heads, d_ff=args.ff, n_kv_heads=args.kv_heads,
        attn_window=args.window, compute_dtype=jnp.bfloat16,
        attn_impl=args.attn,
    )
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, args.vocab, (args.batch, args.prompt)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]

    gen = jax.jit(
        lambda params, prompt: generate(model, params, prompt, args.new)
    )
    out = np.asarray(gen(params, prompt))  # compile + warm
    assert out.shape == (args.batch, args.prompt + args.new)

    times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        np.asarray(gen(params, prompt))  # host transfer = the sync point
        times.append(time.perf_counter() - t0)
    best = min(times)
    n_params = sum(x.size for x in jax.tree.leaves(params))

    quant = None
    if args.quant is not None:
        # Same weights, int8 kernels: decode is weight-HBM-bound, so the
        # tokens/s delta IS the bandwidth story (quality tracked separately
        # by tests/test_quant.py's closeness bounds).
        from tpunet.models import quantize_params

        qmodel = model.clone(weight_quant="int8")
        qparams = quantize_params(params)
        qgen = jax.jit(
            lambda qp, prompt: generate(qmodel, qp, prompt, args.new))
        np.asarray(qgen(qparams, prompt))  # compile + warm
        qtimes = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            np.asarray(qgen(qparams, prompt))
            qtimes.append(time.perf_counter() - t0)
        qbest = min(qtimes)
        quant = {
            "dtype": "int8",
            "wall_s": round(qbest, 4),
            "decode_tok_s": round(args.batch * args.new / qbest, 1),
            "vs_fp": round(best / qbest, 3),
        }

    spec = None
    if args.spec_gamma is not None:
        # An UNTRAINED draft can't agree with an untrained target, so the
        # measured tokens/s here is the acceptance FLOOR. But a round is
        # the same static program whatever gets accepted — acceptance only
        # changes how many rounds run — so the same run also yields the
        # round cost, and with it the perfect-draft CEILING
        # (gamma+1 committed tokens per round). A real (distilled/trained)
        # draft lands between floor and ceiling by its acceptance rate;
        # both bounds are measured hardware numbers, not projections.
        from tpunet.models import speculative_generate

        if args.spec_draft == "quant":
            # The realistic cheap draft: the target itself at int8. Near-fp
            # agreement makes acceptance high, so the measured tokens/s is
            # an honest end-to-end speculative number, not a bound. Reuse
            # the --quant tier's tree when it exists — a second int8 copy
            # would double-count HBM on the bench accounting for it.
            if quant is not None:
                draft, draft_params = qmodel, qparams
            else:
                from tpunet.models import quantize_params

                draft = model.clone(weight_quant="int8")
                draft_params = quantize_params(params)
        else:
            draft = model.clone(n_layers=args.draft_layers)
            draft_params = draft.init(jax.random.PRNGKey(1), prompt)["params"]
        sgen = jax.jit(
            lambda params, dparams, prompt: speculative_generate(
                model, params, draft, dparams, prompt, args.new,
                gamma=args.spec_gamma, per_row=args.spec_per_row,
                return_stats=True))
        out, stats = sgen(params, draft_params, prompt)  # compile + warm
        np.asarray(out)
        stimes = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            out, stats = sgen(params, draft_params, prompt)
            np.asarray(out)  # host transfer = the sync point
            stimes.append(time.perf_counter() - t0)
        sbest = min(stimes)
        rounds = int(stats["rounds"])
        round_s = sbest / rounds
        spec = {
            "gamma": args.spec_gamma,
            "draft": args.spec_draft,
            "per_row": args.spec_per_row,
            **({"draft_layers": args.draft_layers}
               if args.spec_draft == "shallow" else {}),
            "wall_s": round(sbest, 4),
            "rounds": rounds,
            # Shallow-random drafts can't agree with the target, so their
            # measured rate/tokens are the acceptance FLOOR; the quant
            # draft is a real draft and its numbers are plain measurements.
            **({"accept_rate_floor": round(
                    float(stats["draft_accept_rate"]), 4),
                "spec_tok_s_floor": round(args.batch * args.new / sbest, 1)}
               if args.spec_draft == "shallow" else
               {"accept_rate": round(float(stats["draft_accept_rate"]), 4),
                "spec_tok_s": round(args.batch * args.new / sbest, 1),
                "vs_plain": round(best / sbest, 3)}),
            "round_s": round(round_s, 5),
            "spec_tok_s_ceiling": round(
                args.batch * (args.spec_gamma + 1) / round_s, 1),
        }

    print(json.dumps({
        **dev,
        "attn": args.attn,
        "d": args.d, "L": args.layers, "heads": args.heads,
        "kv_heads": args.kv_heads or args.heads,
        "window": args.window,
        "params_M": round(n_params / 1e6, 1),
        "batch": args.batch, "prompt": args.prompt, "new": args.new,
        "wall_s": round(best, 4),
        "decode_tok_s": round(args.batch * args.new / best, 1),
        **({"quant": quant} if quant is not None else {}),
        **({"speculative": spec} if spec is not None else {}),
    }))


if __name__ == "__main__":
    main()
