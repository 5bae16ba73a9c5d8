#!/usr/bin/env python3
"""The quickest proof that tpunet still starts on the chip.

    python chip_smoke.py               one TPU chip, about ten minutes cold
    python chip_smoke.py --four-chips  one process over a four-chip host

Drives the main path once through the entry points a user calls, at the
full width of the model this repository sizes for one v5e chip (vocab 32000,
d2048, L12, h16, ff8192, bf16; weights random from a seed):

  native            build libtpunet.so from cpp/src, load that file, check the
                    XLA FFI handlers are in it, all-reduce 128 MiB between two
                    transport-only processes.
  train             create_train_state + make_train_step driven by
                    tpunet.train.fit; the compiled step holds its 48 flash
                    kernels; the loss is finite and falls.
  decode and serve  jit(generate) with GQA and flash prefill (12 kernels),
                    then a BatchServer whose every token is the reference
                    forward pass's choice, to bf16 rounding.
  dcn               world size 2 against a transport-only peer: jit(dcn_psum)
                    from device buffers, dcn_pmean, and the cross_host train
                    step, whose first loss equals the train phase's.

It finds its device first and exits non-zero, printing no result, unless that
device is a TPU. It never names a platform that would admit the CPU. Every
phase prints one JSON object on a line of its own; a phase that fails makes
the exit code non-zero. The last line of standard output is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process holds the chip: this one. The other ranks of its collectives are
benchmarks.transport_peer processes, which import no JAX. The phases share
one device, so each drops its arrays and programs before the next.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import queue
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import benchmarks  # no JAX in it; in a bare directory this import fails the run

ROOT = Path(__file__).resolve().parent
SEED = 0
DEADLINE_S = 1140  # the contract allows 1200 s, compilation included
KERNEL = 'custom_call_target="tpu_custom_call"'
NEAR_TIE = 2.0 ** -5  # of the top logit: four bf16 steps


@dataclass(frozen=True)
class Sizes:
    """What a run is sized by. main() only ever uses FULL and FOUR_CHIPS;
    the CPU tests pass small ones, with the kernel counts the Pallas
    interpreter gives (none)."""

    model: dict                # Transformer widths and depth
    dtype: str
    train_kernels: int         # flash programs in the compiled train step
    decode_kernels: int        # and in the compiled generate()
    batch: int
    seq: int
    train_steps: int
    kv_heads: int              # decode and serve run grouped-query attention
    dec_batch: int
    prompt: int
    new: int
    serve_slots: int
    serve_max_len: int         # each request generates max_len - len(prompt)
    serve_prompts: tuple
    native_bytes: int
    psum_bytes: tuple
    dcn_steps: int
    chain_dim: int             # the block_until_ready probe: chain_len
    chain_len: int             # dependent (dim x dim) matmuls


FULL = Sizes(
    model=dict(vocab=32000, d_model=2048, n_layers=12, n_heads=16, d_ff=8192),
    dtype="bfloat16",
    train_kernels=48, decode_kernels=12,
    batch=8, seq=2048, train_steps=6,
    kv_heads=4, dec_batch=8, prompt=512, new=256,
    # 256 and 384 tile into the kernel's 128-blocks, so their prefill is the
    # flash kernel; 200 does not, and takes flash_attention's einsum path.
    serve_slots=2, serve_max_len=400, serve_prompts=(256, 384, 200),
    native_bytes=128 << 20,
    psum_bytes=(1 << 20, 16 << 20, 128 << 20),
    dcn_steps=3,
    chain_dim=4096, chain_len=128,
)


@dataclass(frozen=True)
class FourChipSizes:
    model: dict                # d2048 widths; depth cut so that the one-device
    dtype: str                 # reference fits beside the sharded copy
    tp_batch: int              # dp x mdl step: tokens (tp_batch, tp_seq)
    tp_seq: int
    sp_batch: int              # sp ring step: tokens (sp_batch, sp_seq)
    sp_seq: int
    sp_ref_kernels: int        # the one-device sp reference runs flash
    psum_elems: int            # per device
    loss_rtol: float
    spread: float              # bytes_in_use, largest over smallest device


FOUR_CHIPS = FourChipSizes(
    model=dict(vocab=32000, d_model=2048, n_layers=4, n_heads=16, d_ff=8192),
    dtype="bfloat16",
    tp_batch=8, tp_seq=2048,
    # Ring attention's blocks are einsums, not the flash kernel: each holds
    # (heads, s/4, s/4) f32 scores per ring step for the backward pass. At
    # s8192 the step compiles to 16.8 GiB a device, over what a v5e offers.
    sp_batch=1, sp_seq=4096,
    sp_ref_kernels=16,
    psum_elems=4 << 20,
    loss_rtol=1e-2, spread=1.5,
)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# -- the transport-only ranks -------------------------------------------------

_children: list[subprocess.Popen] = []


class Peer:
    """One benchmarks.transport_peer process and its line protocol."""

    def __init__(self):
        env = dict(os.environ)
        env.pop("TPUNET_LIBRARY_PATH", None)  # it loads the file it is told to
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.transport_peer"], cwd=ROOT,
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        _children.append(self.proc)
        self._lines = benchmarks.pump_lines(self.proc)

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def reply(self, timeout: float = 300.0) -> dict:
        try:
            line = self._lines.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(f"peer silent for {timeout}s") from None
        if line is None:
            raise RuntimeError(f"peer exited with {self.proc.wait()}")
        msg = json.loads(line)
        if not msg["ok"]:
            raise RuntimeError(f"peer failed: {msg['error']}")
        return msg

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send(op="close")
                self.proc.stdin.close()
                self.proc.wait(timeout=10)
            except (OSError, ValueError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        if self.proc in _children:
            _children.remove(self.proc)


def stop_children() -> None:
    for proc in list(_children):
        proc.kill()
        proc.wait()
    _children.clear()


def _out_of_time() -> None:
    print(f"chip_smoke: not done after {DEADLINE_S}s, giving up",
          file=sys.stderr, flush=True)
    stop_children()
    os._exit(3)


# -- helpers shared by the phases ----------------------------------------------

def find_device(count: int) -> dict:
    """The device this run is about, as JAX reports it. Anything but `count`
    TPU chips ends the run here, before any phase and with no result line."""
    dev = benchmarks.claim_device()  # places the compile cache; exits off a TPU
    if dev["device_count"] != count:
        raise SystemExit(f"chip_smoke needs {count} TPU chip(s); JAX found {dev}")
    return {"platform": dev["platform"], "kind": dev["device_kind"],
            "count": dev["device_count"]}


def compile_counted(jitted, args, want_kernels: int):
    """Lower and compile `jitted` once; the executable that comes back is the
    one the phase then runs. A program without its Pallas kernels (the
    interpreter's plain ops, or flash_attention's einsum for a shape that
    does not tile) is refused here."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    seconds = time.perf_counter() - t0
    kernels = compiled.as_text().count(KERNEL)
    if kernels != want_kernels:
        raise RuntimeError(f"the compiled program holds {kernels} "
                           f"tpu_custom_call kernels, expected {want_kernels}")
    return compiled, seconds, kernels


def release() -> None:
    """Give the device back between phases: the callers have dropped their
    arrays, this drops the compiled programs."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()


def memory(stat: str):
    import jax

    return [(d.memory_stats() or {}).get(stat) for d in jax.local_devices()]


def train_setup(sz: Sizes, **step_kw):
    """(state, step, tokens, labels, key) for the train phase and for the
    cross-host step of the dcn phase: same seed, so same parameters and
    batch, which is what lets the two first losses be compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpunet.models import Transformer
    from tpunet.train import create_train_state, make_train_step

    model = Transformer(compute_dtype=jnp.dtype(sz.dtype), attn_impl="flash",
                        remat=True, **sz.model)
    tx = optax.adamw(3e-4)
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, sz.model["vocab"],
                                      (sz.batch, sz.seq)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    state, _ = create_train_state(model, jax.random.PRNGKey(SEED), tokens, tx)
    step = make_train_step(model, tx, **step_kw)  # flash, remat, donated
    return state, step, tokens, labels, jax.random.PRNGKey(SEED + 1)


def fit_timed(compiled, state, tokens, labels, key, steps: int):
    """`steps` steps of tpunet.train.fit on the one fixed batch. Returns
    (losses, seconds between the completions of consecutive steps)."""
    import jax

    from tpunet.train import fit

    losses, done_at = [], []

    def step(*args):
        out = compiled(*args)
        jax.block_until_ready(out)
        done_at.append(time.perf_counter())
        return out

    fit(state, step, itertools.repeat((tokens, labels)), steps=steps, rng=key,
        log_every=1, log_fn=lambda m: losses.append(m["loss"]))
    return losses, [b - a for a, b in zip(done_at, done_at[1:])]


def check_losses(losses: list) -> None:
    import math

    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")


# -- phase 1: native -----------------------------------------------------------

def phase_native(sz: Sizes) -> dict:
    from tpunet import _native

    t0 = time.perf_counter()
    lib_path = _native.build_native(force=True)
    build_s = time.perf_counter() - t0
    lib = _native.load(lib_path)  # this file, whatever the environment names

    from tpunet.interop import _FFI_TARGETS

    missing = [s for s in _FFI_TARGETS.values() if not hasattr(lib, s)]
    if missing:
        raise RuntimeError(f"{lib_path} lacks the XLA FFI handlers {missing}")

    coordinator = f"127.0.0.1:{benchmarks.free_port()}"
    world, reps = 2, 4
    ranks = [Peer() for _ in range(world)]
    for r, peer in enumerate(ranks):
        peer.send(op="init", lib=str(lib_path), coordinator=coordinator,
                  rank=r, world=world)
    for peer in ranks:
        peer.reply()
    n = sz.native_bytes // 4
    for r, peer in enumerate(ranks):  # rank r contributes r + 1 everywhere
        peer.send(op="all_reduce", dtype="float32", n=n, fill=r + 1.0,
                  reps=reps, expect=3.0)
    seconds = [peer.reply()["seconds"] for peer in ranks]
    for peer in ranks:
        peer.close()
    # an all-reduce is done when its slowest rank is; the first one also wires
    per_rep = [max(s) for s in zip(*seconds)][1:]
    busbw = 2.0 * (world - 1) / world * sz.native_bytes / min(per_rep) / 1e9
    return {"phase": "native", "compile_s": round(build_s, 2),
            "run_s": round(sum(per_rep), 3), "library": str(lib_path),
            "nproc": os.cpu_count(), "allreduce_bytes": sz.native_bytes,
            "allreduce_busbw_GBps": round(busbw, 3),
            "checked": "library built here and loaded; FFI handlers present; "
                       "2-rank all-reduce equals the expected sum"}


# -- phase 2: train ------------------------------------------------------------

def sync_probe(sz: Sizes) -> dict:
    """Does block_until_ready wait for the device? Time one chain of
    dependent matmuls three ways: to the return of the dispatch, to
    block_until_ready, and to a host transfer of one element."""
    import jax
    import jax.numpy as jnp

    w = jnp.eye(sz.chain_dim, dtype=jnp.dtype(sz.dtype))
    x = jnp.ones((sz.chain_dim, sz.chain_dim), jnp.dtype(sz.dtype))
    chain = jax.jit(lambda x, w: jax.lax.fori_loop(
        0, sz.chain_len, lambda _, x: x @ w, x))
    value = float(chain(x, w)[0, 0])  # compile and warm both ways out

    dispatch_s, ready_s, host_s = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        y = chain(x, w)
        dispatch_s.append(time.perf_counter() - t0)
        y.block_until_ready()
        ready_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        value = float(chain(x, w)[0, 0])
        host_s.append(time.perf_counter() - t0)
    dispatch_s, ready_s, host_s = min(dispatch_s), min(ready_s), min(host_s)
    if value != 1.0:
        raise RuntimeError(f"matmul chain gave {value}, expected 1.0")
    if ready_s < 0.5 * host_s:
        raise RuntimeError(
            f"block_until_ready returned after {ready_s:.4f}s but the result "
            f"took {host_s:.4f}s to reach the host: it does not wait")
    return {"dispatch_s": round(dispatch_s, 5),
            "block_until_ready_s": round(ready_s, 5),
            "host_transfer_s": round(host_s, 5)}


def phase_train(sz: Sizes) -> dict:
    state, step, tokens, labels, key = train_setup(sz)
    compiled, compile_s, kernels = compile_counted(
        step, (state, tokens, labels, key), sz.train_kernels)
    t0 = time.perf_counter()
    losses, step_s = fit_timed(compiled, state, tokens, labels, key,
                               sz.train_steps)
    run_s = time.perf_counter() - t0
    check_losses(losses)
    steady = step_s[1:]  # the first interval may still hold one-time work
    out = {"phase": "train", "compile_s": round(compile_s, 2),
           "run_s": round(run_s, 3), "kernels": kernels,
           "losses": [round(x, 4) for x in losses],
           "step_s": [round(s, 4) for s in step_s],
           "tokens_per_s": round(sz.batch * sz.seq * len(steady) / sum(steady), 1),
           "peak_bytes_in_use": memory("peak_bytes_in_use")[0],
           "sync": sync_probe(sz),
           "checked": f"{kernels} kernels in the executable that ran; loss "
                      "finite and lower after the last step; "
                      "block_until_ready waits for the device"}
    return out


# -- phase 3: decode and serve -------------------------------------------------

def phase_decode_serve(sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpunet.models import BatchServer, Transformer, generate

    vocab = sz.model["vocab"]
    model = Transformer(compute_dtype=jnp.dtype(sz.dtype), attn_impl="flash",
                        n_kv_heads=sz.kv_heads, **sz.model)
    rng = np.random.default_rng(SEED)
    prompt = jnp.asarray(rng.integers(0, vocab, (sz.dec_batch, sz.prompt)),
                         jnp.int32)
    params = jax.jit(
        lambda t: model.init(jax.random.PRNGKey(SEED), t)["params"])(prompt)

    gen = jax.jit(lambda p, t: generate(model, p, t, sz.new))  # greedy
    compiled, compile_s, kernels = compile_counted(
        gen, (params, prompt), sz.decode_kernels)
    first = np.asarray(compiled(params, prompt))
    t0 = time.perf_counter()
    again = np.asarray(compiled(params, prompt))
    generate_s = time.perf_counter() - t0
    if first.shape != (sz.dec_batch, sz.prompt + sz.new):
        raise RuntimeError(f"generate returned shape {first.shape}")
    if not (np.array_equal(first[:, :sz.prompt], np.asarray(prompt))
            and first.min() >= 0 and first.max() < vocab
            and np.array_equal(first, again)):
        raise RuntimeError("generate: prompt not kept, token out of range, "
                           "or two greedy runs differ")

    # A request's reference is the cacheless forward pass over its prompt
    # and the server's own answer: every token the server chose must be,
    # under that reference, the top logit or within bf16 rounding of it
    # (NEAR_TIE of the top logit's size). Token-for-token equality with
    # generate(), which tests/test_serve.py holds in f32 on the CPU, does not
    # survive bf16 on the chip: the 2-slot decode step and generate()'s
    # 1-row step round differently, and a run of near-tied logits flips
    # (first seen on the v5e at generated token 47 of 144, PR 21). How far
    # the two agree is printed, not required. Each request generates up to
    # max_len, so one compiled forward serves all of them.
    forward = jax.jit(lambda p, t: model.apply({"params": p}, t))
    oracle = jax.jit(partial(generate, model),
                     static_argnames=("max_new_tokens",))
    requests = [(rng.integers(0, vocab, p).astype(np.int32),
                 sz.serve_max_len - p) for p in sz.serve_prompts]
    srv = BatchServer(model, params, slots=sz.serve_slots,
                      max_len=sz.serve_max_len)
    ids = [srv.submit(p, n) for p, n in requests]
    t0 = time.perf_counter()
    answers = srv.run()
    serve_s = time.perf_counter() - t0
    agreement = []
    for rid, (p, n) in zip(ids, requests):
        got = np.asarray(answers[rid])
        if got.shape != (n,):
            raise RuntimeError(f"request with prompt {len(p)}: {got.shape} "
                               f"tokens came back, {n} were asked for")
        logits = np.asarray(forward(
            params, jnp.asarray(np.concatenate([p, got]))[None]))[0]
        rows = logits[len(p) - 1:-1]  # row i scores generated token i
        top = rows.max(axis=-1)
        behind = top - rows[np.arange(n), got]
        off = np.flatnonzero(behind > NEAR_TIE * np.maximum(np.abs(top), 1.0))
        if off.size:
            i = int(off[0])
            raise RuntimeError(
                f"BatchServer request with prompt {len(p)}: generated token "
                f"{i} of {n} is {got[i]} with reference logit "
                f"{rows[i, got[i]]:.4f}, but the top is {top[i]:.4f} "
                f"(token {rows[i].argmax()}); {off.size} such tokens")
        want = np.asarray(oracle(params, jnp.asarray(p)[None],
                                 max_new_tokens=n))[0, len(p):]
        differ = np.flatnonzero(got != want)
        agreement.append({
            "prompt": len(p), "new": n,
            "equals_generate_until": int(differ[0]) if differ.size else n,
            "top_logit_tokens": int((behind == 0).sum())})
    served = sum(n for _, n in requests)
    return {"phase": "decode and serve", "compile_s": round(compile_s, 2),
            "run_s": round(generate_s + serve_s, 3), "kernels": kernels,
            "generate_s": round(generate_s, 3),
            "generate_tokens_per_s": round(sz.dec_batch * sz.new / generate_s, 1),
            "serve_s_with_compiles": round(serve_s, 3),
            "serve_requests": agreement,
            "serve_tokens": served, "serve_stats": dict(srv.stats),
            "peak_bytes_in_use": memory("peak_bytes_in_use")[0],
            "checked": f"{kernels} kernels in generate's executable; prompt "
                       "kept, tokens in range, greedy repeatable; every "
                       "token a BatchServer answered with is the reference "
                       "forward pass's top logit or within bf16 rounding "
                       "of it"}


# -- phase 4: dcn --------------------------------------------------------------

def join_world(peer: Peer, lib_path) -> None:
    """World size 2: this process is rank 0 and hosts the bootstrap, the
    transport-only peer is rank 1."""
    from tpunet import distributed

    coordinator = f"127.0.0.1:{benchmarks.free_port()}"
    peer.send(op="init", lib=str(lib_path), coordinator=coordinator,
              rank=1, world=2)
    distributed.initialize(coordinator, 0, 2)
    peer.reply()


def phase_dcn(sz: Sizes, peer: Peer, lib_path, train_first_loss: float) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpunet import distributed
    from tpunet.interop import boundary_chunks, dcn_pmean, dcn_psum

    join_world(peer, lib_path)
    compile_s, t_run = 0.0, time.perf_counter()
    reps, psum = 3, []
    for nbytes in sz.psum_bytes:
        for dtype in ("float32", "bfloat16"):
            n = nbytes // jnp.dtype(dtype).itemsize
            x = jnp.full((n,), 1.5, jnp.dtype(dtype))
            fn, c_s, _ = compile_counted(jax.jit(dcn_psum), (x,), 0)
            compile_s += c_s
            # told only now: the peer must not wait out a compile
            peer.send(op="all_reduce", dtype=dtype, n=n, fill=2.0,
                      reps=reps + 1, expect=3.5)
            y = fn(x).block_until_ready()  # the first call also wires streams
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                y = fn(x).block_until_ready()
                times.append(time.perf_counter() - t0)
            if not bool(jnp.all(y == 3.5)):
                raise RuntimeError(f"dcn_psum {dtype} x {n}: not 1.5 + 2.0")
            ring = peer.reply()["seconds"][1:]  # the peer's view: wire only
            psum.append({"bytes": nbytes, "dtype": dtype,
                         "s_per_call": round(sorted(times)[reps // 2], 5),
                         "peer_s_per_call": round(sorted(ring)[reps // 2], 5)})
            del x, y, fn

    n = 1 << 20
    x = jnp.arange(n, dtype=jnp.float32)
    fn, c_s, _ = compile_counted(jax.jit(dcn_pmean), (x,), 0)
    compile_s += c_s
    peer.send(op="all_reduce", dtype="float32", n=n, fill=0.0, reps=1,
              expect=None)
    if not np.array_equal(np.asarray(fn(x)), np.arange(n, dtype=np.float32) / 2):
        raise RuntimeError("dcn_pmean against a zero peer is not half the vector")
    peer.reply()
    del x, fn

    # The cross-host step: the whole gradient as one flat f32 vector a step,
    # all-reduced a chunk at a time (trainer.py, interop.boundary_chunks); the
    # peer adds zeros of each chunk's length.
    state, step, tokens, labels, key = train_setup(sz, cross_host=True)
    n_grad = sum(x.size for x in jax.tree.leaves(state.params))
    compiled, c_s, kernels = compile_counted(
        step, (state, tokens, labels, key), sz.train_kernels)
    compile_s += c_s
    peer.send(op="all_reduce", dtype="float32",
              n=list(boundary_chunks(n_grad, 4, 2)), fill=0.0,
              reps=sz.dcn_steps, expect=None)
    losses, step_s = fit_timed(compiled, state, tokens, labels, key,
                               sz.dcn_steps)
    peer.reply()
    check_losses(losses)
    if abs(losses[0] - train_first_loss) > 1e-3 * abs(train_first_loss):
        raise RuntimeError(
            f"first cross-host loss {losses[0]} is not the train phase's "
            f"{train_first_loss}: same parameters, same batch")
    distributed.finalize()
    return {"phase": "dcn", "compile_s": round(compile_s, 2),
            "run_s": round(time.perf_counter() - t_run - compile_s, 3),
            "world_size": 2, "psum": psum, "kernels": kernels,
            "cross_host_allreduce_bytes": 4 * n_grad,
            "cross_host_losses": [round(x, 4) for x in losses],
            "cross_host_step_s": [round(s, 3) for s in step_s],
            "peak_bytes_in_use": memory("peak_bytes_in_use")[0],
            "checked": "dcn_psum of 1.5 from the device plus the peer's 2.0 "
                       "is 3.5 everywhere, at every size and dtype; dcn_pmean "
                       "against a zero peer halves the vector; the cross-host "
                       "step's first loss equals the train phase's and the "
                       "loss falls"}



# -- four chips: one process, a mesh over the four devices ---------------------

def sharded_step_vs_one_device(sz: FourChipSizes, devices, axes: dict,
                               batch: int, seq: int, ref_attn: str,
                               ref_kernels: int, attn: str, data_spec,
                               tp_axis, probe_cross_host: bool) -> dict:
    """One seeded train step on one device, then the same state placed over
    a mesh of `axes` and the same step there. Returns both losses and what
    was seen of the placement."""
    import gc
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpunet.models import Transformer, transformer_partition_rules
    from tpunet.parallel import make_named_mesh, shard_params
    from tpunet.train import TrainState, create_train_state, make_train_step

    tx = optax.adamw(3e-4)
    key = jax.random.PRNGKey(SEED + 1)
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, sz.model["vocab"], (batch, seq)),
                         jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)
    ref_model = Transformer(compute_dtype=jnp.dtype(sz.dtype), attn_impl=ref_attn,
                            remat=True, **sz.model)
    state, _ = create_train_state(ref_model, jax.random.PRNGKey(SEED), tokens, tx)
    ref_step, ref_compile_s, _ = compile_counted(
        make_train_step(ref_model, tx, donate=False),
        (state, tokens, labels, key), ref_kernels)
    ref_loss = float(ref_step(state, tokens, labels, key)[1])
    del ref_step

    mesh = make_named_mesh(axes, devices=devices)
    model = ref_model.clone(
        attn_impl=attn, mesh=mesh, tp_axis=tp_axis,
        dp_axis="dp" if "dp" in axes else None, sp_axis="sp")
    rules = transformer_partition_rules(tp_axis=tp_axis)
    shardings = TrainState(shard_params(state.params, mesh, rules),
                           shard_params(state.opt_state, mesh, rules),
                           NamedSharding(mesh, P()))
    state = jax.device_put(state, shardings)
    tokens = jax.device_put(tokens, NamedSharding(mesh, data_spec))
    labels = jax.device_put(labels, NamedSharding(mesh, data_spec))
    gc.collect()  # the one-device copy is gone before memory is read

    # Placement: this code has only ever seen virtual CPU devices, where
    # putting everything on the first one would go unnoticed.
    sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        name = jax.tree_util.keystr(path)
        pieces = math.prod(mesh.shape[a] for a in leaf.sharding.spec if a)
        shards = leaf.addressable_shards
        if (leaf.sharding.device_set != set(mesh.devices.flat)
                or {s.device for s in shards} != set(mesh.devices.flat)):
            raise RuntimeError(f"{name} is not on every device of the mesh")
        if (len({str(s.index) for s in shards}) != pieces
                or any(s.data.shape != leaf.sharding.shard_shape(leaf.shape)
                       for s in shards)):
            raise RuntimeError(
                f"{name} {leaf.shape} under {leaf.sharding.spec}: expected "
                f"{pieces} distinct pieces, got "
                f"{sorted({str(s.index) for s in shards})}")
        sharded += pieces > 1
    if tp_axis and not sharded:
        raise RuntimeError("no parameter was split by the partition rules")
    in_use = memory("bytes_in_use")
    if None in in_use or max(in_use) > sz.spread * min(in_use):
        raise RuntimeError(f"bytes_in_use across the devices: {in_use} "
                           f"(allowed spread {sz.spread}x)")

    out = {"mesh": axes, "tokens": [batch, seq], "attn": attn,
           "params_split": sharded, "bytes_in_use": in_use,
           "ref_attn": ref_attn, "ref_loss": ref_loss,
           "ref_compile_s": round(ref_compile_s, 2)}
    with mesh:
        if probe_cross_host:
            # Outside the pass criteria: recorded, not repaired here.
            try:
                make_train_step(model, tx, cross_host=True).lower(
                    state, tokens, labels, key).compile()
                out["cross_host_sharded_probe"] = "compiles (not run)"
            except Exception as e:  # whatever the partitioner says is the record
                out["cross_host_sharded_probe"] = (
                    f"{type(e).__name__}: {e}"[:600])
        step, compile_s, _ = compile_counted(
            make_train_step(model, tx), (state, tokens, labels, key), 0)
        t0 = time.perf_counter()
        state, loss = step(state, tokens, labels, key)
        loss = float(loss)
        out["run_s"] = round(time.perf_counter() - t0, 3)
    out.update(compile_s=round(compile_s, 2), loss=loss,
               peak_bytes_in_use=memory("peak_bytes_in_use"))
    if not abs(loss - ref_loss) <= sz.loss_rtol * abs(ref_loss):
        raise RuntimeError(f"sharded loss {loss} vs one device {ref_loss} "
                           f"(rtol {sz.loss_rtol}): {out}")
    return out


def four_hierarchical_psum(sz: FourChipSizes, devices, peer: Peer) -> dict:
    """hierarchical_psum under shard_map: lax.psum over the four chips, then
    the DCN all-reduce against the peer, which adds `fill` everywhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpunet.interop import hierarchical_psum

    n, fill = sz.psum_elems, 3.0
    mesh = Mesh(np.array(devices), ("ici",))
    spec = P("ici")
    x = jax.device_put(  # small integers: every sum is exact in f32
        (jnp.arange(len(devices) * n, dtype=jnp.float32) % 1024).reshape(-1, n),
        NamedSharding(mesh, spec))
    ici = jax.jit(jax.shard_map(lambda b: jax.lax.psum(b, "ici"), mesh=mesh,
                                in_specs=spec, out_specs=spec))
    hier, compile_s, _ = compile_counted(
        jax.jit(jax.shard_map(lambda b: hierarchical_psum(b, "ici"), mesh=mesh,
                              in_specs=spec, out_specs=spec)), (x,), 0)
    # Inside shard_map the DCN tier runs once on every device, each with the
    # same already-reduced block: the peer answers as many all-reduces.
    peer.send(op="all_reduce", dtype="float32", n=n, fill=fill,
              reps=len(devices), expect=None)
    t0 = time.perf_counter()
    got = hier(x).block_until_ready()
    run_s = time.perf_counter() - t0
    peer.reply()
    if not bool(jnp.all(got == ici(x) + fill)):
        raise RuntimeError("hierarchical_psum is not lax.psum plus the peer's "
                           f"{fill}")
    return {"compile_s": round(compile_s, 2), "run_s": round(run_s, 3),
            "bytes_per_device": 4 * n, "dcn_allreduces_per_call": len(devices)}


def run_four_chips(sz: FourChipSizes, peer: Peer, devices=None) -> bool:
    import jax
    from jax.sharding import PartitionSpec as P

    from tpunet import _native, distributed

    devices = devices or jax.devices()
    lib_path = _native.build_native(force=True)
    _native.load(lib_path)
    join_world(peer, lib_path)

    def tp():
        return {"phase": "four chips: dp x mdl step", **sharded_step_vs_one_device(
            sz, devices, {"dp": 2, "mdl": 2}, sz.tp_batch, sz.tp_seq,
            ref_attn="reference", ref_kernels=0, attn="reference",
            data_spec=P("dp"), tp_axis="mdl", probe_cross_host=True)}

    def sp():
        return {"phase": "four chips: sp ring step", **sharded_step_vs_one_device(
            sz, devices, {"sp": 4}, sz.sp_batch, sz.sp_seq,
            ref_attn="flash", ref_kernels=sz.sp_ref_kernels, attn="ring",
            data_spec=P(None, "sp"), tp_axis=None, probe_cross_host=False)}

    def hier():
        return {"phase": "four chips: hierarchical_psum",
                **four_hierarchical_psum(sz, devices, peer)}

    ok = run_phases([("four chips: dp x mdl step", tp),
                     ("four chips: sp ring step", sp),
                     ("four chips: hierarchical_psum", hier)])
    distributed.finalize()
    return ok


# -- the runs ------------------------------------------------------------------

def run_phases(phases) -> bool:
    """Run every phase even after one has failed (one call to the chip should
    show every fault), but a failure is never passed over: it is printed
    with its phase and the run ends not ok."""
    import traceback

    ok = True
    for name, fn in phases:
        try:
            emit({"ok": True, **fn()})
        except Exception as e:  # the boundary that reports a failed phase
            traceback.print_exc()
            emit({"ok": False, "phase": name,
                  "error": f"{type(e).__name__}: {e}"[:2000]})
            ok = False
        release()
    return ok


def run_one_chip(sz: Sizes, peer: Peer) -> bool:
    seen: dict = {}

    def native():
        out = phase_native(sz)
        seen["lib"] = out["library"]
        return out

    def train():
        out = phase_train(sz)
        seen["first_loss"] = out["losses"][0]
        return out

    def dcn():
        return phase_dcn(sz, peer, seen["lib"], seen["first_loss"])

    return run_phases([("native", native), ("train", train),
                       ("decode and serve", lambda: phase_decode_serve(sz)),
                       ("dcn", dcn)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip path and what it is compared "
                         "with, and no other phase")
    args = ap.parse_args(argv)

    watchdog = threading.Timer(DEADLINE_S, _out_of_time)
    watchdog.daemon = True
    watchdog.start()
    os.environ.pop("TPUNET_LIBRARY_PATH", None)
    t0 = time.perf_counter()
    try:
        # The rank-1 peer of the dcn phase starts before this process first
        # touches JAX, and waits on its stdin until that phase.
        peer = Peer()
        dev = find_device(4 if args.four_chips else 1)
        emit({"phase": "device", **dev,
              "compile_cache": os.environ["JAX_COMPILATION_CACHE_DIR"]})
        if args.four_chips:
            ok = run_four_chips(FOUR_CHIPS, peer)
        else:
            ok = run_one_chip(FULL, peer)
        peer.close()
    finally:
        stop_children()
    emit({"phase": "total", "seconds": round(time.perf_counter() - t0, 1)})
    emit({"ok": ok, "device": dev})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
