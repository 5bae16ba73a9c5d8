"""Open-loop serving load harness for the disaggregated tier.

Closed-loop drivers (submit, wait, repeat) let a slow server throttle its
own offered load and hide latency cliffs; this harness is OPEN-LOOP: a
Poisson arrival process fixes the offered request rate no matter how the
fleet is doing, so queueing delay and SLO misses show up instead of
evaporating. The workload is shaped like serving, not like a microbench:

  * **Poisson arrivals** at a fixed rate (exponential inter-arrival gaps).
  * **Heavy-tailed prompt lengths** (lognormal), rounded UP into a small
    set of length buckets — the tail is real but the per-length jit
    retrace count stays bounded (one prefill trace per bucket).
  * **Conversation sessions**: a completed request spawns a follow-up
    with probability `session_prob`, its prompt extending the previous
    prompt with the generated tokens (re-bucketed) — the multi-turn
    arrival correlation single-shot load misses.

Latency comes from the tier's OWN SLO histograms (`tpunet_req_ttft_us`,
`tpunet_req_tpot_us` — the same families Prometheus scrapes), so the
harness measures what operators would see, and goodput-at-SLO is the
conservative joint bound: completed rate scaled by the smaller of the
TTFT / TPOT within-SLO fractions.

`run_load()` is the reusable core (the live weight-swap smoke lane drives
it against a fleet mid-publication: `on_tick(elapsed, pump)` fires every
loop pass and `pump` is a bounded poll/submit step a `publish()` call can
interleave between broadcast chunks). The CLI wires a self-contained
in-process two-tier fleet and prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import time


def bucketize(n: int, buckets) -> int:
    """Smallest bucket >= n, else the largest (the cap keeps the lognormal
    tail from minting unbounded distinct prompt lengths -> retraces)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def hist_quantile(bounds, q: float) -> float:
    """Quantile from cumulative histogram buckets [(le, cum_count), ...]
    (telemetry.histogram_buckets): the smallest upper bound covering
    q of the samples — what a Prometheus `histogram_quantile` would pin
    to bucket resolution. inf when the top bucket holds the quantile."""
    total = bounds[-1][1] if bounds else 0
    if total <= 0:
        return float("nan")
    want = math.ceil(q * total)
    for le, cum in bounds:
        if cum >= want:
            return le
    return float("inf")


def hist_frac_within(bounds, slo_us: float) -> float:
    """Fraction of samples at or under `slo_us`, read CONSERVATIVELY from
    the histogram: the cumulative count at the largest bound <= slo_us
    (samples in a bucket straddling the SLO count as misses)."""
    total = bounds[-1][1] if bounds else 0
    if total <= 0:
        return 0.0
    best = 0
    for le, cum in bounds:
        if le <= slo_us:
            best = cum
    return best / total


def run_load(router, *, duration_s: float, rate: float, vocab: int,
             buckets=(8, 16, 32, 64), new_range=(4, 16),
             session_prob: float = 0.3, tail_sigma: float = 0.8,
             seed: int = 0, slo_ttft_us: float = 1_000_000,
             slo_tpot_us: float = 100_000, on_tick=None,
             drain_timeout: float = 240.0) -> dict:
    """Drive `router` under open-loop Poisson load for `duration_s`, then
    drain, and return the measurement dict (see CLI JSON for the keys).

    The caller owns the fleet and the measurement window: reset telemetry
    after warmup, before calling. `on_tick(elapsed_s, pump)` runs once per
    loop pass; `pump()` is one bounded submit/poll/reap step, safe to call
    from inside a `WeightPublisher.publish(pump=...)` so arrivals keep
    flowing while weight bytes stream."""
    import numpy as np

    from tpunet import telemetry
    from tpunet.serve import RouterBusyError

    rng = np.random.default_rng(seed)
    mean_len = math.exp(tail_sigma ** 2 / 2) * buckets[0] * 1.5

    def draw_prompt(prev=None):
        if prev is None:
            raw = int(rng.lognormal(math.log(mean_len), tail_sigma))
        else:
            raw = len(prev)
        plen = bucketize(max(1, raw), buckets)
        prompt = rng.integers(0, vocab, plen).astype(np.int32)
        if prev is not None:  # conversation turn: extend, re-bucket
            keep = min(len(prev), plen)
            prompt[:keep] = prev[-keep:] if keep < len(prev) else prev
        return prompt

    counts = {"offered": 0, "completed": 0, "rejected": 0, "sessions": 0}
    live: dict[int, dict] = {}   # rid -> {"prompt": ..., "max_new": ...}
    seen: set[int] = set()
    t0 = time.monotonic()
    next_arrival = t0 + float(rng.exponential(1.0 / rate))
    followups: list = []

    def submit(prompt):
        counts["offered"] += 1
        max_new = int(rng.integers(new_range[0], new_range[1] + 1))
        try:
            rid = router.submit(prompt, max_new)
        except RouterBusyError:
            counts["rejected"] += 1  # open loop: backpressure drops, not waits
            return
        live[rid] = {"prompt": prompt, "max_new": max_new}

    def reap():
        for rid, tokens in list(router._results.items()):
            if rid in seen or rid not in live:
                continue
            seen.add(rid)
            counts["completed"] += 1
            rec = live.pop(rid)
            if (rng.random() < session_prob
                    and time.monotonic() - t0 < duration_s):
                counts["sessions"] += 1
                followups.append(np.concatenate(
                    [rec["prompt"], np.asarray(tokens, np.int32)]))

    def pump():
        nonlocal next_arrival
        now = time.monotonic()
        while now >= next_arrival and now - t0 < duration_s:
            submit(draw_prompt())
            next_arrival += float(rng.exponential(1.0 / rate))
        while followups:
            submit(draw_prompt(prev=followups.pop()))
        router.poll()
        reap()

    while time.monotonic() - t0 < duration_s:
        pump()
        if on_tick is not None:
            on_tick(time.monotonic() - t0, pump)
        time.sleep(0.001)
    wall_load = time.monotonic() - t0

    deadline = time.monotonic() + drain_timeout
    while live and time.monotonic() < deadline:
        router.poll()
        reap()
        time.sleep(0.001)
    if live:
        raise TimeoutError(
            f"{len(live)} request(s) never completed within {drain_timeout}s "
            f"after the load window")
    wall_total = time.monotonic() - t0

    parsed = telemetry.metrics()
    ttft = telemetry.histogram_buckets("tpunet_req_ttft_us", parsed)
    tpot = telemetry.histogram_buckets("tpunet_req_tpot_us", parsed)
    ttft_ok = hist_frac_within(ttft, slo_ttft_us)
    tpot_ok = hist_frac_within(tpot, slo_tpot_us) if tpot else 1.0
    return {
        "duration_s": round(wall_load, 3),
        "drain_s": round(wall_total - wall_load, 3),
        "offered_rps": round(counts["offered"] / wall_load, 3),
        "achieved_rps": round(counts["completed"] / wall_total, 3),
        **counts,
        "failed": counts["offered"] - counts["completed"]
                  - counts["rejected"],
        "ttft_p50_us": hist_quantile(ttft, 0.50),
        "ttft_p99_us": hist_quantile(ttft, 0.99),
        "tpot_p99_us": hist_quantile(tpot, 0.99),
        "slo_ttft_us": slo_ttft_us, "slo_tpot_us": slo_tpot_us,
        "ttft_ok_frac": round(ttft_ok, 4),
        "tpot_ok_frac": round(tpot_ok, 4),
        # Conservative joint bound: per-request TTFT/TPOT pairing is not
        # recoverable from the histograms, so goodput charges the worse
        # of the two miss fractions against the whole completed rate.
        "goodput_rps": round(
            min(ttft_ok, tpot_ok) * counts["completed"] / wall_total, 3),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--platform", default="tpu", choices=["cpu", "tpu"],
                    help="needs a TPU; cpu is for testing the tool")
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--ff", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kv-codec", default="int8",
                    help="KV wire codec for the shipped blocks")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="open-loop load window, seconds")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="offered arrival rate, requests/second")
    ap.add_argument("--buckets", default="8,16,32,64",
                    help="prompt-length buckets (heavy tail rounds UP "
                         "into these; caps the retrace count)")
    ap.add_argument("--new-min", type=int, default=4)
    ap.add_argument("--new-max", type=int, default=16)
    ap.add_argument("--session-prob", type=float, default=0.3)
    ap.add_argument("--tail-sigma", type=float, default=0.8,
                    help="lognormal sigma of the raw prompt-length draw")
    ap.add_argument("--slo-ttft-us", type=float, default=1_000_000)
    ap.add_argument("--slo-tpot-us", type=float, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    buckets = tuple(int(b) for b in args.buckets.split(","))

    from benchmarks import claim_device

    dev = claim_device(args.platform)
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpunet import serve, telemetry
    from tpunet.models import Transformer

    model = Transformer(
        vocab=args.vocab, d_model=args.d, n_layers=args.layers,
        n_heads=args.heads, d_ff=args.ff,
        compute_dtype=jnp.bfloat16 if args.platform == "tpu"
        else jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, buckets[0]), 0,
                              args.vocab)
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    max_len = buckets[-1] + args.new_max

    lsock = serve.Router.listen("127.0.0.1:0")
    addr = "127.0.0.1:%d" % lsock.getsockname()[1]

    def decode_main():
        worker = serve.connect_decode(addr, model, params, slots=args.slots,
                                      max_len=max_len,
                                      kv_codec=args.kv_codec)
        try:
            worker.serve()
        finally:
            worker.close()

    th = threading.Thread(target=decode_main, daemon=True)
    th.start()
    router = serve.Router(
        serve.PrefillEngine(model, params, max_len=max_len),
        kv_codec=args.kv_codec)
    router.accept_ranks(lsock, 1)
    lsock.close()
    try:
        # Warm every prompt-length bucket (one prefill + decode trace
        # each), then reset so compile time stays out of the histograms.
        for b in buckets:
            router.submit(np.zeros(b, np.int32), 2)
        router.run(timeout=240)
        telemetry.reset()
        out = run_load(
            router, duration_s=args.duration, rate=args.rate,
            vocab=args.vocab, buckets=buckets,
            new_range=(args.new_min, args.new_max),
            session_prob=args.session_prob, tail_sigma=args.tail_sigma,
            seed=args.seed, slo_ttft_us=args.slo_ttft_us,
            slo_tpot_us=args.slo_tpot_us)
        router.run(timeout=60)  # clear the slate before shutdown
    finally:
        router.shutdown()
        th.join(timeout=60)
        router.close()
    print(json.dumps({
        **dev, "slots": args.slots,
        "kv_codec": args.kv_codec, "rate": args.rate,
        "buckets": list(buckets), "session_prob": args.session_prob,
        **out}))


if __name__ == "__main__":
    main()
