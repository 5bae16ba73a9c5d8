"""The flat cross-host train step as two device programs with the gradient's
all-reduce between them on the host (make_train_step(cross_host=True)):
bit-identical to the single program that held dcn_pmean(flat), which stays
here as the oracle; what the ahead-of-time compile gives back; the bridge's
counters and spans once a step. Since PR 29 the vector crosses in chunks:
every case runs with the vector whole (the tiny model is under one chunk of
the shipped size) and again cut into CHUNKED bytes a chunk. Since PR 31 a
chunk's host block stays under the allocator's mmap ceiling and the first
boundary step tells the allocator to keep freed blocks."""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

# Module level so mp-spawn children (which re-import this module, but not
# conftest.py) are held to the CPU too.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from conftest import free_port, run_spawn_workers  # noqa: E402

STEPS = 3
# bytes a chunk for the cut cases: the tiny model's gradient (N_GRAD f32) in
# K_CHUNKED chunks, the last one ragged
CHUNKED, N_GRAD, K_CHUNKED = 4096, 3120, 4
BRIDGE_CHILDREN = ["dcn.bridge.stage_in", "dcn.bridge.collective", "dcn.bridge.stage_out"]


def _in_jit_step(model, tx, donate: bool, grad_compression=None, accum_steps=None):
    """The step as it was before the boundary: ONE program, the flat
    gradient through dcn_pmean in its middle (an FFI custom call on the CPU,
    an ordered io_callback with TPUNET_FFI_COLLECTIVES=0)."""
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from tpunet.interop import dcn_pmean
    from tpunet.train.trainer import TrainState, _apply_updates, _value_and_grads

    def train_step(state, images, labels, dropout_rng):
        loss, grads = _value_and_grads(model, state.params, images, labels,
                                       dropout_rng, 0.01, None, accum_steps, 0.0)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        f0 = [leaf.dtype == jax.dtypes.float0 for leaf in leaves]
        flat, unravel = ravel_pytree(
            [leaf for leaf, skip in zip(leaves, f0) if not skip])
        if grad_compression == "bf16":
            reduced = dcn_pmean(flat.astype(jnp.bfloat16)).astype(flat.dtype)
        else:
            reduced = dcn_pmean(flat)
        it = iter(unravel(reduced))
        grads = jax.tree_util.tree_unflatten(
            treedef, [leaf if skip else next(it) for leaf, skip in zip(leaves, f0)])
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = _apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def _tiny(rank: int, qlora: bool = False):
    """(model, tx, a fresh state's maker, tokens, labels); the ranks differ
    in their batch, so the mean over them is what couples them."""
    import jax.numpy as jnp
    import optax

    from tpunet.models import Transformer, graft_base, lora_optimizer, quantize_params
    from tpunet.train import TrainState

    model = Transformer(vocab=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
                        compute_dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(10 + rank), (4, 8), 0, 32)
    labels = jnp.roll(toks, -1, axis=1)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    tx = optax.adamw(1e-2)
    if qlora:  # frozen int8 leaves: their gradients are float0
        model = model.clone(weight_quant="int8", lora_rank=4)
        params = graft_base(model.init(jax.random.PRNGKey(2), toks)["params"],
                            quantize_params(params))
        tx = lora_optimizer(optax.adam(5e-3), params)

    def fresh():  # a donating step eats its state: one each
        p = jax.tree.map(jnp.copy, params)
        return TrainState(p, tx.init(p), jnp.zeros((), jnp.int32))

    return model, tx, fresh, toks, labels


def _run(step, state, toks, labels):
    losses = []
    for i in range(STEPS):
        state, loss = step(state, toks, labels, jax.random.PRNGKey(i))
        losses.append(np.asarray(loss))
    return jax.tree.map(np.asarray, state), losses


def _assert_bitwise(got, want) -> None:
    (gs, gl), (ws, wl) = got, want
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(ws)]
    for path, a, b in zip(paths, jax.tree.leaves(gs), jax.tree.leaves(ws), strict=True):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), path
    assert [x.tobytes() for x in gl] == [x.tobytes() for x in wl]


CASES = {
    "donate-ffi": dict(donate=True),
    "keep-ffi": dict(donate=False),
    "donate-callback": dict(donate=True, ffi="0"),
    "keep-callback": dict(donate=False, ffi="0"),
    "float0-leaves": dict(donate=False, qlora=True),
    "bf16-ffi": dict(donate=True, grad_compression="bf16"),
    "bf16-callback": dict(donate=False, grad_compression="bf16", ffi="0"),
    "accum2": dict(donate=True, accum_steps=2),
}


def _parity_worker(rank: int, world: int, port: int, q, case: str,
                   chunk_bytes: int | None) -> None:
    try:
        kw = dict(CASES[case])
        os.environ["TPUNET_FFI_COLLECTIVES"] = kw.pop("ffi", "1")
        qlora = kw.pop("qlora", False)
        from tpunet import distributed, interop, telemetry
        from tpunet.train import make_train_step

        if chunk_bytes:
            interop._CHUNK_BYTES = chunk_bytes
        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        model, tx, fresh, toks, labels = _tiny(rank, qlora)
        want = _run(_in_jit_step(model, tx, **kw), fresh(), toks, labels)

        def count(family: str) -> float:
            return sum(telemetry.metrics()[family].values())

        before = count("tpunet_bridge_calls_total")
        got = _run(make_train_step(model, tx, cross_host=True, **kw), fresh(),
                   toks, labels)
        # the boundary is taken whatever the oracle's bridge was
        assert count("tpunet_bridge_calls_total") - before == STEPS
        if chunk_bytes and not qlora:  # LoRA's gradient is its adapters' alone
            assert count("tpunet_bridge_chunks_total") == STEPS * len(
                interop.boundary_chunks(
                    N_GRAD, 2 if kw.get("grad_compression") else 4, world))
        # two ranks' sum is one addition an element however the vector is cut
        _assert_bitwise(got, want)
        if qlora:
            assert any(leaf.dtype == np.int8 for leaf in jax.tree.leaves(got[0].params))
        assert not np.array_equal(got[1][0], got[1][-1])  # it did train
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        import traceback

        q.put((rank, f"FAIL: {type(e).__name__}: {e}\n{traceback.format_exc()[-800:]}"))


@pytest.mark.parametrize("chunk_bytes", [None, CHUNKED], ids=["whole", "chunked"])
@pytest.mark.parametrize("case", list(CASES))
def test_three_steps_bit_identical_to_the_in_jit_path(case, chunk_bytes):
    run_spawn_workers(_parity_worker, 2, extra_args=(case, chunk_bytes))


def _world4_worker(rank: int, world: int, port: int, q) -> None:
    try:
        from tpunet import distributed, interop
        from tpunet.train import make_train_step

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        model, tx, fresh, toks, labels = _tiny(rank)
        whole = _run(make_train_step(model, tx, cross_host=True), fresh(), toks, labels)
        interop._CHUNK_BYTES = CHUNKED
        cut = _run(make_train_step(model, tx, cross_host=True), fresh(), toks, labels)
        # four terms an element, added in the order of the ring segment the
        # element falls in: the last bit may differ, no more
        for a, b in zip(jax.tree.leaves(cut[0].params), jax.tree.leaves(whole[0].params),
                        strict=True):
            assert np.linalg.norm(a - b) <= 1e-6 * np.linalg.norm(b)
        np.testing.assert_allclose(cut[1], whole[1], rtol=1e-6)
        assert not np.array_equal(cut[1][0], cut[1][-1])
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        import traceback

        q.put((rank, f"FAIL: {type(e).__name__}: {e}\n{traceback.format_exc()[-800:]}"))


def test_four_ranks_chunked_equal_whole_to_the_last_bits():
    run_spawn_workers(_world4_worker, 4)


# -- one rank, in this process ---------------------------------------------------

@pytest.fixture()
def world_of_one():
    from tpunet import distributed

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    yield
    distributed.finalize()


def _bridge_counts() -> dict:
    from tpunet import telemetry

    m = telemetry.metrics()
    return {(fam.split("_")[2], telemetry.labels(key)["kind"]): v
            for fam in ("tpunet_bridge_calls_total", "tpunet_bridge_bytes_total")
            for key, v in m[fam].items() if v}


def _n_grad(state) -> int:
    return sum(x.size for x in jax.tree.leaves(state.params))


@pytest.fixture(params=[None, CHUNKED], ids=["whole", "chunked"])
def chunk_bytes(request, monkeypatch):
    """The vector whole (None: the shipped chunk size, far above the tiny
    model) and cut into chunks of CHUNKED bytes."""
    from tpunet import interop

    if request.param:
        monkeypatch.setattr(interop, "_CHUNK_BYTES", request.param)
    return request.param


def test_chunk_sizes_from_the_vectors_bytes_and_the_world():
    from tpunet import interop
    from tpunet.interop import boundary_chunks

    per = interop._CHUNK_BYTES // 4
    assert boundary_chunks(N_GRAD, 4, 2) == (N_GRAD,)  # under one chunk: whole
    assert boundary_chunks(0, 4, 2) == (0,)
    assert boundary_chunks(per, 4, 4) == (per,)
    assert boundary_chunks(2 * per + 5, 4, 4) == (per, per, 5)  # a ragged last one
    vgg = boundary_chunks(138_357_544, 4, 4)
    assert sum(vgg) == 138_357_544 and set(vgg[:-1]) == {per} and 0 < vgg[-1] < per
    assert boundary_chunks(138_357_544, 2, 2)[0] == 2 * per  # bytes, not elements
    # every rank's share of a whole chunk is a whole number of 64-byte lines
    for world in (2, 3, 4, 5, 8):
        for itemsize in (2, 4):
            first = boundary_chunks(1 << 30, itemsize, world)[0]
            assert first * itemsize % (64 * world) == 0
            assert first * itemsize <= interop._CHUNK_BYTES


@pytest.mark.parametrize("itemsize", [2, 4])
def test_no_block_reaches_the_allocators_ceiling(itemsize):
    """A block whose allocation reaches glibc's DEFAULT_MMAP_THRESHOLD_MAX is
    a fresh mapping every step whatever the allocator is told."""
    from tpunet import interop

    assert interop._MMAP_CEILING == 4 * 1024 * 1024 * 8
    room = interop._MMAP_CEILING - interop._ALLOC_MARGIN
    assert interop._CHUNK_BYTES <= room
    for world in range(1, 9):
        for size in (1, N_GRAD, 138_357_544, (1 << 30) + 7):
            blocks = interop.boundary_chunks(size, itemsize, world)
            assert sum(blocks) == size and max(blocks) * itemsize <= room


class _FakeLibc:
    """Stands where interop looks for the C library: counts mallopt's calls."""

    def __init__(self, has_mallopt: bool = True):
        self.calls, self.c_int = [], None
        if has_mallopt:
            self.mallopt = lambda param, value: self.calls.append((param, value)) or 1

    def CDLL(self, name):
        assert name is None  # the process's own C library
        return self


@pytest.fixture()
def fake_libc(request, monkeypatch):
    from tpunet import interop

    libc = _FakeLibc(*getattr(request, "param", ()))
    monkeypatch.setattr(interop, "ctypes", libc)
    interop.retain_freed_host_blocks.cache_clear()
    yield libc
    interop.retain_freed_host_blocks.cache_clear()  # the next caller asks the real one


def test_the_allocator_is_told_once_and_by_a_boundary_step_alone(world_of_one,
                                                                 fake_libc):
    from tpunet import interop
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    key = jax.random.PRNGKey(0)
    for kw in (dict(), dict(cross_host=True, bucket_bytes=1 << 10)):
        make_train_step(model, tx, **kw)(fresh(), toks, labels, key)
    assert fake_libc.calls == []  # a step that is one program never asks
    step = make_train_step(model, tx, cross_host=True)
    told = [(-3, interop._MMAP_CEILING), (-1, 2 ** 31 - 1),
            (-2, 2 * interop._MMAP_CEILING)]  # <malloc.h>'s M_MMAP_THRESHOLD, ...
    assert fake_libc.calls == told
    # the compiled twin, a second step and a direct call find it done
    step.lower(fresh(), toks, labels, key).compile()(fresh(), toks, labels, key)
    make_train_step(model, tx, cross_host=True, donate=False)
    assert interop.retain_freed_host_blocks() is True
    assert fake_libc.calls == told


@pytest.mark.parametrize("fake_libc", [(False,)], indirect=True, ids=["no-mallopt"])
def test_a_c_library_without_mallopt_is_left_alone(world_of_one, fake_libc):
    from tpunet import interop
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    want = _run(_in_jit_step(model, tx, donate=True), fresh(), toks, labels)
    got = _run(make_train_step(model, tx, cross_host=True), fresh(), toks, labels)
    assert interop.retain_freed_host_blocks() is False and fake_libc.calls == []
    _assert_bitwise(got, want)


FAULT_STEPS = 8


def _faults_worker(rank: int, world: int, port: int, q) -> None:
    try:
        import flax.linen as nn
        import jax.numpy as jnp
        import optax

        from tpunet import distributed, interop, telemetry
        from tpunet.train import TrainState, make_train_step

        n = 4 * interop._CHUNK_BYTES // 4  # four blocks of the shipped size

        class OneLeaf(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                w = self.param("w", nn.initializers.zeros, (n,), jnp.float32)
                z = jnp.vdot(w, x)
                return jnp.stack([z, jnp.zeros_like(z)])[None]

        distributed.initialize(f"127.0.0.1:{port}", rank, world)
        params = {"w": jnp.zeros((n,), jnp.float32)}
        tx = optax.sgd(0.01)
        state = TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))
        step = make_train_step(OneLeaf(), tx, cross_host=True)
        x, labels = jnp.ones((n,), jnp.float32), jnp.zeros((1,), jnp.int32)
        per_mib = []
        for i in range(FAULT_STEPS):
            telemetry.reset()
            state, _ = step(state, x, labels, jax.random.PRNGKey(i))
            m = telemetry.metrics()
            assert sum(m["tpunet_bridge_chunks_total"].values()) == 4
            per_mib.append(sum(m["tpunet_bridge_minor_faults_total"].values())
                           / (sum(m["tpunet_bridge_bytes_total"].values()) / 2 ** 20))
        # The first step's blocks are pages new to the process, one fault
        # every 4 KiB (fewer where the host backs them with huge pages); once
        # each arena that serves them has grown to hold them, none. A loose
        # factor: which of the runtime's threads allocates varies.
        assert per_mib[0] > 8 and min(per_mib[4:]) <= per_mib[0] / 4, per_mib
        distributed.finalize()
        q.put((rank, "OK"))
    except Exception as e:  # noqa: BLE001
        import traceback

        q.put((rank, f"FAIL: {type(e).__name__}: {e}\n{traceback.format_exc()[-800:]}"))


def test_a_cpu_rank_stops_faulting_once_its_heap_holds_the_blocks():
    run_spawn_workers(_faults_worker, 1)


def test_the_programs_hand_over_a_tuple_of_chunks(world_of_one, chunk_bytes):
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    state = fresh()
    assert _n_grad(state) == N_GRAD
    step = make_train_step(model, tx, cross_host=True, donate=False)
    loss, chunks = step._grad(state, toks, labels, jax.random.PRNGKey(0))
    want = (1024, 1024, 1024, 48) if chunk_bytes else (N_GRAD,)
    assert isinstance(chunks, tuple) and tuple(c.shape for c in chunks) == tuple(
        (n,) for n in want) and len(want) == (K_CHUNKED if chunk_bytes else 1)
    step(state, toks, labels, jax.random.PRNGKey(0))
    # every chunk's slice of the kept buffer starts on a 64-byte line
    assert step._out.shape == (N_GRAD,) and step._out.ctypes.data % 64 == 0
    # The way back: on the CPU backend device_put is an alias, so the vector
    # comes back whole; an accelerator gets the reduced chunks one by one,
    # and the apply program joins them to the same vector.
    from tpunet.interop import host_all_reduce, host_buffer_like, reduced_like

    back = host_all_reduce(chunks, host_buffer_like(step._out))
    assert [b.shape for b in back] == [(N_GRAD,)]
    assert [(s.shape, s.dtype) for s in reduced_like(
        chunks, [c.sharding for c in chunks])] == [((N_GRAD,), chunks[0].dtype)]
    _assert_bitwise((step._apply(state, tuple(chunks)), []),
                    (step._apply(state, back), []))


class _DotModel:
    """Stands for a model: its logits are (<params, x>, 0), x a tree like the
    parameters, so at zero parameters every gradient is -x/2 exactly (the
    -1/2 made at run time), whichever program computes it. Integer leaves
    take no part: their gradients are float0."""

    def apply(self, variables, x, train=False, rngs=None, mutable=False):
        import jax.numpy as jnp

        z = sum(jnp.vdot(w, v) for w, v in zip(jax.tree.leaves(variables["params"]),
                                               jax.tree.leaves(x), strict=True)
                if jnp.issubdtype(w.dtype, jnp.inexact))
        return jnp.stack([z, jnp.zeros_like(z)])[None]


# name: (shape, dtype) of each parameter, the grad_compression, and the chunks'
# sizes at CHUNKED bytes a chunk
_MIXED = {"a": ((1000,), "float32"), "b": ((300,), "float32"), "c": ((10, 10), "float32"),
          "d": ((50,), "float32"), "e": ((4, 50), "float32"), "f": ((1500,), "float32")}
CUT_CASES = {
    # a spans chunks 0 to 2
    "leaf-over-three-chunks": ({"a": ((2500,), "float32"), "b": ((40, 30), "float32")},
                               None, (1024, 1024, 1024, 628)),
    # chunk 1: the end of b, all of c, d and e, the start of f
    "whole-leaves-and-two-parts": (_MIXED, None, (1024, 1024, 1024, 78)),
    # QLoRA's frozen int8 base: a float0 gradient between two that are cut
    "float0-leaf": ({"a": ((1000,), "float32"), "b": ((64,), "int8"),
                     "c": ((600,), "float32")}, None, (1024, 576)),
    "bf16": (_MIXED, "bf16", (2048, 1102)),
    # ravel_pytree promotes: the bfloat16 leaf crosses as float32
    "mixed-dtypes": ({"a": ((1000,), "bfloat16"), "b": ((1500,), "float32")},
                     None, (1024, 1024, 452)),
    "under-one-chunk": ({"a": ((100,), "float32"), "b": ((20, 3), "float32")},
                        None, (160,)),
}


@pytest.mark.parametrize("case", list(CUT_CASES))
def test_the_chunks_are_the_raveled_vectors_split(world_of_one, monkeypatch, case):
    """The grad program cuts each chunk from slices of the leaves (PR 34):
    what it hands over is, element for element, the split of the vector
    ravel_pytree makes of them."""
    import jax.numpy as jnp
    import optax
    from jax.flatten_util import ravel_pytree

    from tpunet import interop
    from tpunet.interop import boundary_chunks
    from tpunet.train import TrainState, make_train_step
    from tpunet.train.trainer import _value_and_grads

    layout, compression, sizes = CUT_CASES[case]
    monkeypatch.setattr(interop, "_CHUNK_BYTES", CHUNKED)
    params = {k: jnp.zeros(shape, dtype) for k, (shape, dtype) in layout.items()}
    x = {k: jax.random.normal(jax.random.PRNGKey(i), shape).astype(dtype)
         for i, (k, (shape, dtype)) in enumerate(layout.items())}
    labels, key = jnp.zeros((1,), jnp.int32), jax.random.PRNGKey(0)
    step = make_train_step(_DotModel(), optax.sgd(0.1), cross_host=True,
                           grad_compression=compression)
    _, chunks = step._grad(TrainState(params, (), jnp.zeros((), jnp.int32)),
                           x, labels, key)

    _, grads = _value_and_grads(_DotModel(), params, x, labels, key, 0.01, None, None)
    flat, _ = ravel_pytree([g for g in jax.tree.leaves(grads)
                            if g.dtype != jax.dtypes.float0])
    if compression:
        flat = flat.astype(jnp.bfloat16)
    assert boundary_chunks(flat.size, flat.dtype.itemsize, 1) == sizes
    want = jnp.split(flat, np.cumsum(sizes)[:-1])
    assert [c.shape for c in chunks] == [(n,) for n in sizes]
    for got, w in zip(chunks, want, strict=True):
        assert got.dtype == w.dtype and np.asarray(got).tobytes() == np.asarray(w).tobytes()
    assert np.any(np.asarray(flat) != 0)


def test_the_cut_makes_no_vector_sized_temporaries(monkeypatch):
    """VGG16's 32 leaves (553 MB of float32) over two ranks at the shipped
    chunk size, the gradient scaled at run time. Joined into one vector and
    split, the grad program needed 0.89 GB of temporaries (PERF.md, PR 31):
    a fresh mapping every step. Cut from the leaves, 58 MB (PR 34)."""
    import jax.numpy as jnp
    import optax

    from tpunet import distributed
    from tpunet.models import vgg16
    from tpunet.train import TrainState, make_train_step

    monkeypatch.setattr(distributed, "world_size", lambda: 2)
    params = jax.eval_shape(lambda: vgg16().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))["params"])
    assert sum(x.size for x in jax.tree.leaves(params)) == 138_357_544
    step = make_train_step(_DotModel(), optax.sgd(0.1), cross_host=True)
    state = TrainState(params, (), jax.ShapeDtypeStruct((), jnp.int32))
    grad = step._grad.lower(state, params, jax.ShapeDtypeStruct((1,), jnp.int32),
                            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    assert len(grad.output_shardings[1]) == 17  # the cells' cut
    assert grad.memory_analysis().temp_size_in_bytes < 128 * 10**6


def test_lowered_and_compiled_is_callable_and_holds_both_programs(world_of_one,
                                                                  chunk_bytes):
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    step = make_train_step(model, tx, cross_host=True)
    key = jax.random.PRNGKey(0)
    compiled = step.lower(fresh(), toks, labels, key).compile()
    text = compiled.as_text()
    assert "jit_grad_program" in text and "jit_apply_program" in text
    # nothing of either program leaves the device in its middle
    for mark in ("callback", "is_host_transfer", "custom_call_target=\"tpunet",
                 "send-done", "recv-done"):
        assert mark not in text, mark
    _assert_bitwise(_run(compiled, fresh(), toks, labels),
                    _run(step, fresh(), toks, labels))
    # abstract arguments lower too (tests/test_chip_compile.py compiles so)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          (fresh(), toks, labels, key))
    assert "jit_apply_program" in step.lower(*shapes).compile().as_text()


@pytest.mark.parametrize("grad_compression,itemsize", [(None, 4), ("bf16", 2)],
                         ids=["f32", "bf16"])
def test_one_bridge_call_and_the_vectors_bytes_a_step(world_of_one, grad_compression,
                                                      itemsize, chunk_bytes):
    from tpunet import telemetry
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    step = make_train_step(model, tx, cross_host=True,
                           grad_compression=grad_compression)
    state = fresh()
    n = _n_grad(state)
    telemetry.reset()
    _run(step, state, toks, labels)
    assert _bridge_counts() == {("calls", "all_reduce"): STEPS,
                                ("bytes", "all_reduce"): STEPS * itemsize * n}
    # K chunks a step; bf16 halves the bytes. In flight at one time: the
    # chunk waited for and the copies that run ahead of it, 2 or more if K > 1
    from tpunet import interop

    k = -(-itemsize * n // chunk_bytes) if chunk_bytes else 1
    assert k == (1 if not chunk_bytes else K_CHUNKED if itemsize == 4 else 2)
    m = telemetry.metrics()
    assert sum(m["tpunet_bridge_chunks_total"].values()) == STEPS * k
    deepest = sum(m["tpunet_bridge_chunks_in_flight_max"].values())
    assert deepest == min(k, interop._COPIES_AHEAD + 1) and (k == 1 or deepest >= 2)


@pytest.mark.parametrize("ahead,deepest", [(0, 1), (1, 2), (2, 3), (8, K_CHUNKED)])
def test_copies_out_run_a_bounded_number_of_chunks_ahead(world_of_one, monkeypatch,
                                                         ahead, deepest):
    """Chunk k and the `ahead` chunks after it are on their way out while
    chunk k is waited for and reduced: that many and no more, whatever K is;
    the result does not depend on it."""
    from tpunet import interop, telemetry
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    whole = _run(make_train_step(model, tx, cross_host=True, donate=False),
                 fresh(), toks, labels)
    monkeypatch.setattr(interop, "_CHUNK_BYTES", CHUNKED)
    monkeypatch.setattr(interop, "_COPIES_AHEAD", ahead)
    telemetry.reset()
    cut = _run(make_train_step(model, tx, cross_host=True, donate=False),
               fresh(), toks, labels)
    _assert_bitwise(cut, whole)
    m = telemetry.metrics()
    assert sum(m["tpunet_bridge_chunks_total"].values()) == STEPS * K_CHUNKED
    assert sum(m["tpunet_bridge_chunks_in_flight_max"].values()) == deepest


def test_bridge_spans_nest_once_a_step_under_fit(world_of_one, tmp_path, chunk_bytes):
    from tpunet import telemetry
    from tpunet.train import fit, make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    step = make_train_step(model, tx, cross_host=True)
    state = fresh()
    n = _n_grad(state)
    with telemetry.profile(str(tmp_path)):
        fit(state, step, [(toks, labels)] * STEPS, steps=STEPS, log_every=1,
            log_fn=lambda m: None)
    (path,) = glob.glob(os.path.join(str(tmp_path), "tpunet-trace-rank*.json"))
    with open(path) as f:
        events = [e for e in json.load(f) if e.get("ph") == "X"]
    bridges = [e for e in events if e["name"] == "dcn.bridge"]
    assert len(bridges) == STEPS
    steps = {e["args"]["seq"]: e for e in events if e["name"] == "train.step"}
    for b in bridges:
        # the exchange runs on the thread that called the step, inside it
        assert b["args"]["parent"] == "train.step_fn" and b["args"]["seq"] in steps
        assert b["args"]["kind"] == "all_reduce" and b["args"]["nbytes"] == 4 * n
        kids = sorted((e for e in events if e["args"].get("parent") == "dcn.bridge"
                       and e["args"]["seq"] == b["args"]["seq"]), key=lambda e: e["ts"])
        # the three stages once a chunk, chunk after chunk on this thread
        n_chunks = K_CHUNKED if chunk_bytes else 1
        assert [k["name"] for k in kids] == BRIDGE_CHILDREN * n_chunks
        assert [k["args"]["chunk"] for k in kids] == [
            c for c in range(n_chunks) for _ in BRIDGE_CHILDREN]
        assert sum(k["args"]["nbytes"] for k in kids
                   if k["name"] == "dcn.bridge.collective") == 4 * n
        assert all(b["ts"] <= k["ts"] and k["ts"] + k["dur"] <= b["ts"] + b["dur"]
                   and k["tid"] == b["tid"] for k in kids)
    assert len({b["args"]["seq"] for b in bridges}) == STEPS


def test_the_communicator_is_resolved_at_every_call(world_of_one):
    """Elastic recovery re-points the process-default communicator under
    programs that are already compiled."""
    from tpunet import distributed
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    compiled = make_train_step(model, tx, cross_host=True, donate=False).lower(
        fresh(), toks, labels, jax.random.PRNGKey(0)).compile()
    want = _run(compiled, fresh(), toks, labels)
    first = distributed.global_communicator()
    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    assert distributed.global_communicator() is not first
    _assert_bitwise(_run(compiled, fresh(), toks, labels), want)


@pytest.mark.parametrize("kw", [dict(), dict(cross_host=True, bucket_bytes=1 << 10)],
                         ids=["one-host", "bucketed"])
def test_the_other_steps_are_still_one_jitted_program(world_of_one, kw):
    from tpunet.train import make_train_step

    model, tx, fresh, toks, labels = _tiny(0)
    step = make_train_step(model, tx, **kw)
    assert type(step) is type(jax.jit(lambda: 0))
    assert "jit_train_step" in step.lower(fresh(), toks, labels,
                                          jax.random.PRNGKey(0)).as_text()


def test_the_result_buffer_is_kept_and_free_again_when_a_call_returns(world_of_one,
                                                                      chunk_bytes):
    """The ring reduces into ONE buffer a step object, 64-byte aligned (the
    CPU backend's device_put then aliases it). Calls that no data flow
    chains (the same state twice) must not see each other's bytes: a call
    returns only when its apply program has read the buffer."""
    from tpunet.train import make_train_step

    from tpunet import distributed

    model, tx, fresh, toks, labels = _tiny(0)
    step = make_train_step(model, tx, cross_host=True, donate=False)
    state, key = fresh(), jax.random.PRNGKey(0)
    other = jax.random.randint(jax.random.PRNGKey(99), toks.shape, 0, 32)
    first = jax.tree.map(np.asarray, step(state, toks, labels, key))
    buf = step._out
    assert buf.ctypes.data % 64 == 0 and buf.nbytes == 4 * _n_grad(state)
    # every ring of a later step is handed a part of that buffer, each part
    # once a step, and only when no apply program is running: the step
    # before has returned, so its state is ready
    comm = distributed.global_communicator()
    ring, handed, last = comm.all_reduce, [], {}

    def watched(arr, op="sum", inplace=False, out=None):
        assert np.shares_memory(out, buf) and out.ctypes.data % 64 == 0
        assert "state" not in last or last["state"].step.is_ready()
        handed.append(out.ctypes.data)
        return ring(arr, op, inplace, out)

    comm.all_reduce = watched
    for _ in range(3):
        last["state"], _ = step(state, other, labels, key)  # another gradient, same buffer
        again = jax.tree.map(np.asarray, step(state, toks, labels, key))
        assert step._out is buf
        for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(first), strict=True):
            assert a.tobytes() == b.tobytes()
    k = K_CHUNKED if chunk_bytes else 1
    assert len(handed) == 6 * k and len(set(handed)) == k


def test_all_reduce_into_a_callers_buffer(world_of_one):
    from tpunet import distributed
    from tpunet.interop import host_buffer_like

    comm = distributed.global_communicator()
    x = np.arange(1000, dtype=np.float32)
    out = host_buffer_like(x)
    assert out.shape == x.shape and out.dtype == x.dtype and out.ctypes.data % 64 == 0
    assert comm.all_reduce(x, "sum", out=out) is out
    np.testing.assert_array_equal(out, x)
    for bad in (np.empty(999, np.float32), np.empty(1000, np.float64),
                np.empty(2000, np.float32)[::2], [0.0] * 1000):
        with pytest.raises(ValueError, match="out must be"):
            comm.all_reduce(x, "sum", out=bad)
    frozen = np.empty(1000, np.float32)
    frozen.flags.writeable = False
    with pytest.raises(ValueError, match="out must be"):
        comm.all_reduce(x, "sum", out=frozen)
    with pytest.raises(ValueError, match="pass no out"):
        comm.all_reduce(x.copy(), "sum", inplace=True, out=out)
