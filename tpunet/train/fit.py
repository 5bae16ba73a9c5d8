"""High-level training driver: data -> step -> checkpoint -> resume.

`fit()` is the convenience loop tying the framework's pieces together the
way the benchmarks do by hand: a (possibly prefetched) batch iterator, the
jitted train step from `make_train_step`, periodic orbax checkpoints with
exact resume, and a metrics hook. It stays deliberately thin — every
capability (DCN tier, ZeRO, accumulation, fused xent) is configured on the
step function itself, so fit() composes with all of them instead of
re-exposing their knobs.

The reference has no trainer at all (it is a transport; its end-to-end
validation drove an external synthetic benchmark — reference
README.md:52-84). This is framework capability above it.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable

import jax

from tpunet import telemetry
from tpunet.train.checkpoint import CheckpointManager
from tpunet.train.trainer import TrainState


def fit(
    state: TrainState,
    train_step: Callable,
    batches: Iterable,
    *,
    steps: int,
    rng=None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    max_to_keep: int = 3,
    log_every: int = 0,
    log_fn: Callable[[dict[str, Any]], None] | None = None,
    eval_every: int = 0,
    eval_fn: Callable[[TrainState], dict[str, Any]] | None = None,
    skip_batches_on_resume: bool = False,
    prefetch: int = 0,
    prefetch_sharding=None,
) -> TrainState:
    """Run `steps` optimizer steps (counted by state.step, so a resumed run
    finishes the SAME total schedule, not `steps` more).

    state: from create_train_state (resume is handled here when
        checkpoint_dir holds a checkpoint — the freshly-initialized state
        supplies structure and shardings for the restore).
    train_step: make_train_step(...)-style (state, inputs, labels, rng) ->
        (state, loss).
    batches: yields (inputs, labels); pass `prefetch=2` to overlap
        host->HBM transfer (fit wraps the stream itself, after any resume
        skip).
    rng: PRNGKey folded with the step counter for per-step dropout keys.
    checkpoint_every: save every k steps (and once at the end) when
        checkpoint_dir is set; 0 = only the final save.
    log_fn: called with {"step", "loss", "steps_per_s"} every `log_every`
        steps (default print), AND — when eval_fn is set — with
        {"step", "eval": {...}} records at eval points: log_fn
        implementations must dispatch on the presence of the "eval" key.
        Loss is fetched to host ONLY at log/final steps — fetching every
        step would serialize dispatch.
    eval_fn: called with the CURRENT state every `eval_every` steps (and
        once after the final step); its returned metrics dict is passed to
        log_fn with the step under {"step", "eval": {...}}. Run your eval
        set inside it with a jitted eval step — fit() stays agnostic to
        what "evaluation" means. eval_every=0 with an eval_fn set means
        final-step evaluation only.
    skip_batches_on_resume: when resuming at step k, first discard k
        batches from the iterator, so a deterministic stream (e.g.
        token_batches with a fixed seed) lines up exactly where the
        interrupted run left off and the resumed trajectory matches an
        uninterrupted one. Leave False for stateful/streaming sources that
        manage their own position.
    prefetch: when > 0, wrap the batch stream in
        tpunet.data.prefetch_to_device(size=prefetch) — HERE, after the
        resume skip, so skipped batches are a cheap host-side index
        advance, never materialized or transferred. Prefer this over
        wrapping `batches` yourself when also using
        skip_batches_on_resume. prefetch_sharding is passed through
        (e.g. batch_sharding(mesh)).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    mgr = (
        CheckpointManager(checkpoint_dir, max_to_keep=max_to_keep)
        if checkpoint_dir
        else None
    )
    try:
        if mgr is not None:
            restored = mgr.restore_latest(state)
            # Adopt the checkpoint only when it is AHEAD of the caller's
            # state: a caller that already restored a newer state from
            # elsewhere (e.g. elastic recovery choosing the most advanced
            # member checkpoint) must not be silently rolled back by an
            # older local checkpoint. Explicit rollback = restore manually.
            if restored is not None and int(restored.step) > int(state.step):
                state = restored

        def _default_log(m):
            if "eval" in m:
                print(f"[fit] step {m['step']} eval {m['eval']}", flush=True)
            else:
                print(f"[fit] step {m['step']} loss {m['loss']:.4f} "
                      f"({m['steps_per_s']:.2f} steps/s)", flush=True)

        log = log_fn or _default_log
        it = iter(batches)
        loss = None
        t0 = time.perf_counter()
        # Host-side mirror of state.step: reading the device scalar every
        # iteration (int(state.step)) would sync per step and serialize
        # dispatch — fetched ONCE here (post-restore), then incremented
        # locally in lockstep with the step function's step+1.
        done = int(state.step)
        start_step = done
        window_start = done
        last_eval_step = -1
        if skip_batches_on_resume and done:
            for _ in range(done):
                next(it, None)
        if prefetch > 0:
            from tpunet.data import prefetch_to_device

            it = prefetch_to_device(it, size=prefetch,
                                    sharding=prefetch_sharding)
        # Spans (docs/DESIGN.md 6c): one train.step an iteration, on the
        # profiler's step view by step_num; inside it the places where the
        # host can hold the device back. A stream that ends early leaves a
        # last train.step holding only its train.feed.
        while done < steps:
            with telemetry.span("train.step", step_num=done):
                try:
                    with telemetry.span("train.feed"):
                        inputs, labels = next(it)
                except StopIteration:
                    break  # finite dataset exhausted before the schedule
                step_rng = jax.random.fold_in(rng, done)
                with telemetry.span("train.step_fn"):
                    state, loss = train_step(state, inputs, labels, step_rng)
                done += 1
                if log_every and done % log_every == 0:
                    dt = time.perf_counter() - t0
                    with telemetry.span("train.loss_fetch"):
                        loss_host = float(loss)  # host transfer = the sync point
                    log({
                        "step": done,
                        "loss": loss_host,
                        "steps_per_s": (done - window_start) / dt if dt > 0 else 0.0,
                    })
                    t0 = time.perf_counter()
                    window_start = done
                if (eval_fn is not None and eval_every
                        and done % eval_every == 0 and done < steps):
                    with telemetry.span("train.eval"):
                        evaluated = eval_fn(state)
                    log({"step": done, "eval": evaluated})
                    last_eval_step = done
                    # Eval wall time must not deflate the NEXT window's
                    # steps_per_s: restart the throughput window after it.
                    t0 = time.perf_counter()
                    window_start = done
                if mgr is not None and checkpoint_every and done % checkpoint_every == 0:
                    with telemetry.span("train.checkpoint"):
                        mgr.save(done, state)
        if eval_fn is not None and done > start_step and done != last_eval_step:
            # Final evaluation on the finished state (also covers runs whose
            # stream ended early) — skipped for pure no-op re-invocations and
            # when the cadence already evaluated this exact step (a stream
            # exhausted right at an eval point must not eval twice).
            with telemetry.span("train.eval"):
                evaluated = eval_fn(state)
            log({"step": done, "eval": evaluated})
        if mgr is not None:
            if done == start_step and start_step < steps:
                # The schedule wanted more steps but the stream yielded
                # none: still leave an artifact — a silent no-op run with a
                # configured checkpoint_dir would otherwise be undetectable.
                # (A re-invoked COMPLETED run — start_step >= steps — is a
                # legitimate no-op, not this case.)
                import warnings

                warnings.warn(
                    f"fit() ran 0 steps (state.step={done}, steps={steps}): "
                    "the batch stream was empty; ensuring a checkpoint "
                    "exists for the current state",
                    stacklevel=2,
                )
            # Skip when the cadence already saved this exact step: orbax's
            # force=True bypasses the save-interval policy but still raises
            # StepAlreadyExistsError on a duplicate step.
            if mgr.latest_step() != done:
                with telemetry.span("train.checkpoint"):
                    mgr.save(done, state, force=True)
            mgr.wait_until_finished()
    finally:
        if mgr is not None:
            mgr.close()
    return state
