"""One rank of a training cell: set up, the checked first steps, the window,
and on rank 0 the comparison with the plain reference. adapters/train_step
runs it in its own process as the only rank; adapters/dp_ranks starts one
process a rank.

The first three steps and the window are ONE tpunet.train.fit() loop over
ONE compiled step and its state: fit() is what a user calls, with
log_every=1, so the loss of every step reaches the host and the loop keeps
time with the device. The window closes at the first step boundary after
--seconds. The ranks of a job agree on that boundary through `ctl`: rank 0
decides, before each step, and tells the others one byte.
"""

from __future__ import annotations

import os
import time

from perfbench import compare, harness, optimizers, traffic as traffic_mod, weights
from perfbench.adapters import _models
from perfbench.references import train_follow

CHECK_STEPS = train_follow.STEPS


def _first_grad(opt_state, opt: dict, keep=()) -> tuple[dict, dict]:
    """Per-leaf norm of the first gradient as the optimizer got it, from its
    state after one step (perfbench/optimizers/<name>.py says where it
    stands there), and the leaves named in `keep` whole, on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tree, scale = optimizers.find(opt).first_grad(opt_state, opt)
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) * scale, t))
    flat = weights.flatten(_models._plain(tree))
    whole = {p: np.asarray(flat[p], np.float32) * scale for p in keep}
    return norms(tree), whole


def _delta_norms(params, spec: dict, seed: int) -> dict:
    """Per-leaf norm of (parameters now) - (parameters the seed gives), a
    leaf at a time so that no second copy of the model is made."""
    import jax
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    flat = weights.flatten(params)
    out = {}
    for path, (shape, std) in spec.items():
        fn = jax.jit(lambda p, k, path=path, shape=shape, std=std: jnp.sqrt(jnp.sum(
            jnp.square(p - weights._leaf(k, path, shape, std, jnp.float32)))))
        out[path] = fn(flat[path], key)
    return {p: float(v) for p, v in out.items()}


def run_rank(cell: dict, seed: int, seconds: float, trace: bool, *,
             platform: str, rank: int, world: int, is_twin: bool,
             ctl_read: int | None, ctl_write: list[int], fault: str | None = None):
    """Returns the result dict on rank 0, None elsewhere."""
    t0 = harness.process_start()
    cfg, mix, opt = cell["config"], cell["traffic"], cell["optimizer"]
    device = harness.claim_device(1, platform)
    device["count"] = cell["chips"] if platform != "cpu" else device["count"]
    import jax
    import jax.numpy as jnp

    from tpunet.train import TrainState, fit, make_train_step

    cross_host = world > 1
    if cross_host:
        from tpunet import distributed

        with harness.stage(f"rank {rank}: join the world of {world}"):
            distributed.initialize(cell["_coordinator"], rank, world)

    ref = _models.reference(cfg)
    spec = ref.param_spec(cfg)
    real = _models.build(cfg, cell)
    with harness.stage(f"rank {rank}: weights, batches, state"):
        shapes = _models.program_shapes(real, traffic_mod.sample_input(mix, cfg))
        _models.check_spec(shapes, spec)
        if not is_twin:
            pool = traffic_mod.train_batches(mix, cfg, seed, rank=rank)
        if is_twin:
            from perfbench import twin

            model = twin.Twin(twin.children_of(shapes))
            norm = cell["twin_grad_norm"]
            inputs = jax.jit(lambda: {p: twin.pattern(seed, p, shape, norm)
                                      for p, (shape, _) in spec.items()})()
            pool = [(inputs, jnp.zeros((1,), jnp.int32))]
            params = {"tree": jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes)}
        else:
            model = real
            params = weights.generate(spec, seed, jnp.float32)
        tx = optimizers.find(opt).program(opt)
        state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
        key = jax.random.fold_in(weights.seed_key(seed), 7)
        del params

    with harness.stage(f"rank {rank}: compile the step"):
        step = make_train_step(model, tx, cross_host=cross_host)
        compiled = step.lower(state, *pool[0], key).compile()
        kernels = harness.count_kernels(
            compiled, None if is_twin or platform == "cpu" else cell.get("kernels"))

    # -- the loop's own feed and call ------------------------------------------
    tw = harness.TraceWindow(trace and rank == 0, cell["name"], seed)
    losses: list[float] = []
    done_at: list[float] = []
    probe = {}
    box = {"deadline": None, "trace_stop_at": None, "native": None}

    def call(state, inputs, labels, rng):
        with harness.annotate("in_step_program"):
            if fault == "half_batch" and not is_twin:  # the second half left out, the mean over the rest
                inputs, labels = jax.tree.map(
                    lambda x: jnp.concatenate([x[: x.shape[0] // 2]] * 2), (inputs, labels))
            kept = jax.tree.map(jnp.copy, state) if fault == "state_unchanged" else None
            out = compiled(state, inputs, labels, rng)
            if kept is not None:  # a step that returns its state unchanged
                out = (kept, out[1])
            if fault == "loss_altered":
                out = (out[0], out[1] * 1.01)
            jax.block_until_ready(out[1])
        done_at.append(time.perf_counter())
        if len(done_at) == 1 and rank == 0 and not is_twin:
            probe["grad_norm"], probe["grad_leaf"] = _first_grad(
                out[0].opt_state, opt, cell.get("grad_diff_leaves", ()))
        return out

    def feed(limit: int | None):
        """limit: that many batches; None: until rank 0's clock says stop."""
        k = len(done_at)
        while True:
            if limit is not None:
                if k >= limit:
                    return
            elif rank == 0:
                now = time.perf_counter()
                if tw.started and not tw.stopped and now >= box["trace_stop_at"]:
                    _stop_trace()
                go = now < box["deadline"]
                for fd in ctl_write:
                    os.write(fd, b"G" if go else b"S")
                if not go:
                    return
            elif os.read(ctl_read, 1) != b"G":
                return
            yield pool[k % len(pool)]
            k += 1

    def _stop_trace():
        tw.stop()
        if box["native"] is not None:
            box["native"].__exit__(None, None, None)
            box["native"] = None

    log = lambda m: losses.append(m["loss"])  # noqa: E731
    with harness.stage(f"rank {rank}: first {CHECK_STEPS} steps"):
        state = fit(state, call, feed(CHECK_STEPS), steps=CHECK_STEPS, rng=key,
                    log_every=1, log_fn=log)
        if rank == 0 and not is_twin:
            probe["loss"] = list(losses)
            probe["grad_norm"] = {p: float(v) for p, v in weights.flatten(
                jax.device_get(probe["grad_norm"])).items()}
            probe["delta_norm"] = _delta_norms(state.params, spec, seed)

    counters0 = _counters() if cross_host else None
    native_dir = os.path.join(tw.dir, "native")
    if tw.on:
        tw.start()
        if cross_host:
            from tpunet import telemetry

            box["native"] = telemetry.profile(native_dir)
            box["native"].__enter__()
    setup_s = time.time() - t0
    t_start = time.perf_counter()
    box["deadline"] = t_start + seconds
    box["trace_stop_at"] = t_start + min(cell.get("trace_seconds", seconds), seconds)
    n_before = len(done_at)
    state = fit(state, call, feed(None), steps=10 ** 9, rng=key, log_every=1,
                log_fn=log)
    jax.block_until_ready(state)
    window_s = time.perf_counter() - t_start
    _stop_trace()
    steps = len(done_at) - n_before
    if rank != 0:
        return None
    counters1 = _counters() if cross_host else None
    peak, peak_parts = harness.memory_peak()
    step_ends = [t - t_start for t in done_at[n_before:]]

    # -- metrics ---------------------------------------------------------------
    pk = harness.peaks(device["kind"]) if platform != "cpu" else None
    run = {"steps": steps, "window_s": window_s, "step_s": window_s / max(steps, 1),
           "setup_s": setup_s, "kernels": kernels, "step_ends": step_ends,
           "units_per_step": traffic_mod.units_per_step(mix),
           "counters": _delta(counters0, counters1), "native_dir": native_dir,
           "world": world, "losses_window": losses[CHECK_STEPS:]}
    metrics, bd, dev = {}, None, dict(device, memory_peak_bytes=peak)
    if not trace:
        unit = harness.units()
        for name in harness.metric_names(cell["name"], traced=False):
            # "step_s.dcn" is the record's "step_s" under the name the cells
            # with a cross-host step report it by
            metrics[name] = {"value": run[name.split(".")[0]], "unit": unit[name]}
    else:
        t, lo, hi = tw.reduce(cell.get("trace_patterns"))
        traced = [e for e in step_ends if e <= tw.t_stop - t_start + 1e-9]
        run["traced_steps"] = len(traced)
        run["trace_t0"], run["trace_t1"] = tw.t_start, tw.t_stop
        if traced:  # stopping the profiler costs the rest of the window time
            run["step_s"] = traced[-1] / len(traced)
        ctx = {"trace": t, "lo": lo, "hi": hi, "run": run, "cell": cell,
               "peaks": pk, "chips": cell["chips"]}
        metrics = harness.per_layer(cell["name"], ctx)
        bd = harness.breakdown(t, lo, hi)
        tw.cleanup()
        from perfbench import trace as trace_mod

        dev["busy_s"] = trace_mod.busy_seconds(t, lo, hi)
        dev["window_s"] = hi - lo

    # -- the comparison, once the program's state is freed -----------------------
    del state, compiled, step, pool
    harness.release()
    with harness.stage("reference: three steps in float32"):
        ref_out = reference_steps(cell, seed, "f32")
    readings, where = compare.train(probe, ref_out)
    ok, compared = compare.judge(readings, cell["limits"])
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    ok = ok and finite and steps > 0
    step_times = [b - a for a, b in zip([0.0] + step_ends, step_ends)]
    slowest = max(range(len(step_times)), key=step_times.__getitem__) if step_times else None
    extra = {"steps": steps, "window_s": window_s, "kernels": kernels,
             "slowest_step": None if slowest is None else
             {"index": slowest, "seconds": step_times[slowest],
              "median_seconds": sorted(step_times)[len(step_times) // 2]},
             "worst_leaves": where, "loss_first_steps": probe["loss"],
             "reference_loss": ref_out["loss"], "losses_finite": finite,
             "memory": peak_parts, "roofline_bound": run.get("roofline_bound"),
             "kernel_calls": run.get("kernel_calls"),
             "roofline_skipped": run.get("roofline_skipped")}
    return harness.result(ok, steps, 0 if finite else 1, metrics, dev, compared,
                          bd, extra)


def reference_steps(cell: dict, seed: int, precision: str,
                    fault: str | None = None) -> dict:
    """The reference (or, at a lower precision, the control) through the
    cell's first three steps, on the default device."""
    from perfbench import twin

    cfg, mix = cell["config"], cell["traffic"]
    ref = _models.reference(cfg)
    spec = ref.param_spec(cfg)
    chip_ranks = cell["chips"]
    rank_batches = [traffic_mod.train_batches(mix, cfg, seed, rank=r,
                                              count=CHECK_STEPS)
                    for r in range(chip_ranks)]
    twins = cell.get("ranks", chip_ranks) - chip_ranks
    if twins > 1:
        raise SystemExit("the reference forms the mean with at most one twin")
    peer = None
    if twins:
        norm = cell["twin_grad_norm"]
        peer = lambda path, shape: twin.grad_leaf(seed, path, shape, norm)  # noqa: E731
    return train_follow.follow(ref, cfg, spec, seed, rank_batches,
                               cell["optimizer"], cell["reference_rows"],
                               precision, peer, bool(cell.get("reference_offload")),
                               fault, tuple(cell.get("grad_diff_leaves", ())))


def _counters() -> dict:
    from tpunet import telemetry

    return {name: sum(series.values()) for name, series in telemetry.metrics().items()}


def _delta(a: dict | None, b: dict | None) -> dict:
    if a is None or b is None:
        return {}
    return {k: b[k] - a.get(k, 0.0) for k in b}
