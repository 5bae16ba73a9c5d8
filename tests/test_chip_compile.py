"""Compiles for a described TPU v5e, without the chip: the kernels and the
whole programs of the main path at the widths chip_smoke.py runs them at
(d2048 L12 ff8192 h16, vocab 32000, bf16).

These are compiles, not runs: the TPU compiler is installed here and
compiles for a topology that is described and not attached. They catch what
interpret mode cannot: a slice the tiling refuses, a kernel that wants more
VMEM than a core has, a step that does not fit HBM, a program that holds no
kernel at all. One file, one process: only one process may load libtpu, and
it keeps it until it exits.
"""

import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import FULL, KERNEL

HBM_BYTES = 16 * 1024**3  # one v5e chip
MODEL = FULL.model  # vocab 32000, d2048, L12, h16, ff8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_chip(monkeypatch):
    """The program asks `jax.default_backend()` which path to lower, and
    here that is the CPU. Steer it the way the chip would: Pallas kernels
    compiled by Mosaic, DCN collectives over io_callback. Through
    sys.modules, because `tpunet.ops` re-exports a function named
    flash_attention over the submodule of that name."""
    import tpunet.interop  # noqa: F401
    import tpunet.ops  # noqa: F401

    import tpunet.ops.dsa_attention  # noqa: F401
    import tpunet.ops.grouped_matmul  # noqa: F401
    import tpunet.ops.ssd_scan  # noqa: F401

    for kernels in ("flash_attention", "grouped_matmul", "dsa_attention", "ssd_scan"):
        monkeypatch.setattr(sys.modules[f"tpunet.ops.{kernels}"],
                            "_auto_interpret", lambda: False)
    monkeypatch.setattr(sys.modules["tpunet.interop"],
                        "_ffi_available", lambda: False)


def _on(sharding, tree):
    """Shapes of `tree`, placed on the described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _flash_loss(causal=True, window=None):
    from tpunet.ops import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal, interpret=False, window=window)
        return jnp.sum(o.astype(jnp.float32))

    return loss


def _qkv(one_chip, batch, seq, kv_heads, heads=16):
    def arr(n):
        return jax.ShapeDtypeStruct((batch, seq, n, 128), jnp.bfloat16,
                                    sharding=one_chip)

    return arr(heads), arr(kv_heads), arr(kv_heads)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("kv_heads,window", [(16, None), (4, None),
                                             (16, 256), (4, 256)],
                         ids=["mha", "gqa4", "window256", "gqa4_window256"])
def test_flash_b8_s2048(one_chip, direction, kv_heads, window):
    fn = _flash_loss(window=window)
    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv(one_chip, 8, 2048, kv_heads)).compile().as_text()
    assert text.count(KERNEL) == (1 if direction == "fwd" else 3)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_b1_s8192(one_chip, direction):
    fn = _flash_loss()
    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1, 2))
    jax.jit(fn).lower(*_qkv(one_chip, 1, 8192, 16)).compile()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_mistral_b2_s8192_window4096(one_chip, direction):
    """The benchmark's Mistral cell, blocks left to the plan (512 x 512
    there): forward stays one kernel and backward two more, which the
    cell's harness counts."""
    from tpunet.ops.flash_attention import _plan

    assert _plan(8192, 8192, jnp.bfloat16, True, 4096) == (512, 512, 108)
    fn = _flash_loss(window=4096)
    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv(one_chip, 2, 8192, 8, heads=32)).compile().as_text()
    assert text.count(KERNEL) == (1 if direction == "fwd" else 3)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window4096"])
def test_flash_smallthinker_b2_s8192_group7(one_chip, direction, window):
    """The SmallThinker cell's two kinds of layer in one step: 28 query
    heads over 4 key-value heads (a group of 7), causal with and without
    the window; the plan gives each call its own visit count."""
    from tpunet.ops.flash_attention import _plan

    assert _plan(8192, 8192, jnp.bfloat16, True, window) == (
        512, 512, 136 if window is None else 108)
    fn = _flash_loss(window=window)
    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*_qkv(one_chip, 2, 8192, 4, heads=28)).compile().as_text()
    assert text.count(KERNEL) == (1 if direction == "fwd" else 3)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("k,n", [(2560, 768), (768, 2560)], ids=["gate_up", "down"])
def test_grouped_matmul_smallthinker_rows98304_groups16(one_chip, direction, k, n):
    """The SmallThinker cell's grouped products: 16 held experts, a buffer
    for the worst case of 16,384 tokens x 6 choices (98,304 rows and a tile
    an expert), bf16 with float32 accumulation, the matrices float32. A
    group's whole matrix sits in VMEM. The kernels carry their own names
    into the HLO, which the benchmark's readers match."""
    from tpunet.ops import grouped_matmul as gm

    tile_m = gm.tile_rows(98304, jnp.bfloat16)
    rows = gm.buffer_rows(98304, 16, tile_m)
    assert (tile_m, rows) == (512, 106496)
    assert gm._plan(tile_m, k, n, 2) == (k, n)

    def fn(x, w, tile_group, n_tiles):
        out = gm.grouped_matmul(x, w, tile_group, n_tiles, tile_m=tile_m,
                                interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1))
    shapes = [((rows, k), jnp.bfloat16), ((16, k, n), jnp.float32),
              ((rows // tile_m,), jnp.int32), ((1,), jnp.int32)]
    text = jax.jit(fn).lower(*(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                               for s, d in shapes)).compile().as_text()
    names = ["moe_gmm_fwd"] if direction == "fwd" else ["moe_gmm_dx", "moe_tgmm_dw"]
    assert text.count(KERNEL) == len(names)
    for name in names:
        assert len([ln for ln in text.splitlines()
                    if name in ln.split(" = ")[0] and KERNEL in ln]) == 1, name


def test_smallthinker_train_step_has_its_kernels_and_fits(one_chip, as_on_chip):
    """The benchmark's SmallThinker configuration through make_train_step
    at the cell's b2 x s8192: a layer holds 4 flash and 12 grouped kernels
    (forward, remat's recompute, backward), and weights, AdamW state and the
    step's scratch fit one chip."""
    import optax

    from perfbench import harness
    from perfbench.models import smallthinker
    from tpunet.train import TrainState, make_train_step

    cfg = harness.load("configs", "smallthinker-21b-a3b-ep4-l4")
    layers = cfg["num_hidden_layers"]
    model = smallthinker.build(cfg, {"remat": True})
    tx = optax.adamw(3e-4)
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def state_of(t):
        params = model.init(jax.random.PRNGKey(0), t)["params"]
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    state = jax.eval_shape(state_of, tokens)
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 656_529_920
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = make_train_step(model, tx).lower(
        _on(one_chip, state), tokens, tokens, _on(one_chip, key)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == layers * 16
    for name, count in (("moe_gmm_fwd", 6), ("moe_gmm_dx", 3), ("moe_tgmm_dw", 3)):
        assert len([ln for ln in text.splitlines() if KERNEL in ln
                    and name in ln.split(" = ")[0]]) == layers * count, name
    assert _device_bytes(compiled) < HBM_BYTES


def _named_kernels(text: str, name: str) -> int:
    return len([ln for ln in text.splitlines()
                if KERNEL in ln and name in ln.split(" = ")[0]])


@pytest.mark.parametrize("what", ["index", "attn_fwd", "attn_bwd"])
def test_dsa_keye_b2_s8192_h32_kv4(one_chip, what):
    """The selecting attention's kernels at the Keye cell's shapes: a
    16 x 64 indexer over one key head, 32 query heads on 4 key heads of 128,
    the mask's (512, 8192) int8 slab beside a head's whole K and V."""
    from tpunet.ops import dsa_attention as dsa

    b, s = 2, 8192
    shape = lambda dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dt, sharding=one_chip)
    if what == "index":
        fn = lambda qi, ki, w: dsa.index_scores(qi, ki, w, interpret=False)  # noqa: E731
        args = (shape((b, s, 16, 64)), shape((b, s, 64)), shape((b, s, 16), jnp.float32))
        names = ["dsa_index_fwd"]
    else:
        loss = lambda q, k, v, m: jnp.sum(dsa.selected_attention(  # noqa: E731
            q, k, v, m, interpret=False).astype(jnp.float32))
        fn = loss if what == "attn_fwd" else jax.grad(loss, (0, 1, 2))
        args = (shape((b, s, 32, 128)), shape((b, s, 4, 128)), shape((b, s, 4, 128)),
                shape((b, s, s), jnp.int8))
        names = ["dsa_attn_fwd"] + (["dsa_attn_dq", "dsa_attn_dkv"] if what == "attn_bwd" else [])
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count(KERNEL) == len(names)
    for name in names:
        assert _named_kernels(text, name) == 1, name


def test_keye_train_step_has_its_kernels_and_fits(one_chip, as_on_chip):
    """The benchmark's Keye configuration through make_train_step at the
    cell's b2 x s8192: a layer holds 2 dsa_index_fwd and 2 dsa_attn_fwd
    (forward and remat's recompute), dq and dkv once, and 12 grouped kernels;
    weights, AdamW state and the step's scratch fit one chip."""
    import optax

    from perfbench import harness
    from perfbench.models import keye
    from tpunet.train import TrainState, make_train_step

    cfg = harness.load("configs", "keye-vl2-30b-a3b-ep8-l4")
    layers = cfg["num_hidden_layers"]
    model = keye.build(cfg, {"remat": True})
    tx = optax.adamw(3e-4)
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def state_of(t):
        params = model.init(jax.random.PRNGKey(0), t)["params"]
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    state = jax.eval_shape(state_of, tokens)
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 465_391_104
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = make_train_step(model, tx).lower(
        _on(one_chip, state), tokens, tokens, _on(one_chip, key)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) >= harness.load("workloads", "keye-train-s8192")["kernels"]
    for name, count in (("dsa_index_fwd", 2), ("dsa_attn_fwd", 2), ("dsa_attn_dq", 1),
                        ("dsa_attn_dkv", 1), ("moe_gmm_fwd", 6), ("moe_gmm_dx", 3),
                        ("moe_tgmm_dw", 3)):
        assert _named_kernels(text, name) == layers * count, name
    assert _device_bytes(compiled) < HBM_BYTES


def test_nemotron_train_step_has_its_kernels_and_fits(one_chip, as_on_chip):
    """The benchmark's Nemotron-H configuration through make_train_step at
    the cell's b2 x s8192: a Mamba block holds ssd_fwd twice (forward and
    remat's recompute) and ssd_bwd once, an expert block 4 grouped forward
    kernels and 4 backward ones, an attention block flash's; weights, AdamW
    state and the step's scratch fit one chip."""
    import optax

    from perfbench import harness
    from perfbench.models import nemotron_h
    from tpunet.train import TrainState, make_train_step

    cfg = harness.load("configs", "nemotron3-super-120b-a12b-tp8-l11")
    model = nemotron_h.build(cfg, {"remat": True})
    tx = optax.adamw(3e-4)
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def state_of(t):
        params = model.init(jax.random.PRNGKey(0), t)["params"]
        return TrainState(params, tx.init(params), jnp.zeros((), jnp.int32))

    state = jax.eval_shape(state_of, tokens)
    assert sum(x.size for x in jax.tree.leaves(state.params)) == nemotron_h.params(cfg)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = make_train_step(model, tx).lower(
        _on(one_chip, state), tokens, tokens, _on(one_chip, key)).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) >= harness.load("workloads", "nemotron-train-s8192")["kernels"]
    mamba, experts = (nemotron_h.layers_of(cfg, k) for k in "ME")
    for name, count in (("ssd_fwd", 2 * mamba), ("ssd_bwd", mamba),
                        ("moe_gmm_fwd", 4 * experts), ("moe_gmm_dx", 2 * experts),
                        ("moe_tgmm_dw", 2 * experts)):
        assert _named_kernels(text, name) == count, name
    assert _device_bytes(compiled) < HBM_BYTES


def test_flash_s32768_is_refused_for_vmem(one_chip):
    """Each program stages the whole K and V of its head in VMEM, so the
    sequence a kernel can take is bounded by the core's 16 MiB. This pins
    the limit until a later PR streams K/V through the grid."""
    with pytest.raises(Exception, match=r"(?is)vmem.*limit"):
        jax.jit(_flash_loss()).lower(*_qkv(one_chip, 1, 32768, 16)).compile()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("seq", [16384, 32768])
def test_eva_h32_w2048_c16(one_chip, direction, seq):
    """EvaByte's attention at its published widths, half and the whole of
    its published context: each program stages one window of K and V (or
    the summaries), so the sequence does not bound it. The kernels carry
    their own names into the HLO, which the benchmark's readers match."""
    from tpunet.ops.eva_attention import eva_attention

    def fn(q, k, v, phi, mu):
        o = eva_attention(q, k, v, phi, mu, 2048, 16, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    if direction == "bwd":
        fn = jax.grad(fn, argnums=(0, 1, 2, 3, 4))
    qkv = jax.ShapeDtypeStruct((1, seq, 32, 128), jnp.bfloat16, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((32, 128), jnp.float32, sharding=one_chip)
    text = jax.jit(fn).lower(qkv, qkv, qkv, vec, vec).compile().as_text()
    names = ["eva_local_fwd", "eva_remote_fwd"]
    if direction == "bwd":
        names += ["eva_local_dq", "eva_remote_dq", "eva_local_dkv", "eva_remote_dkv"]
    assert text.count(KERNEL) == len(names)
    for name in names:
        assert len([ln for ln in text.splitlines()
                    if name in ln.split(" = ")[0] and KERNEL in ln]) == 1, name


def _train_program(one_chip, **step_kw):
    import optax

    from tpunet.models import Transformer
    from tpunet.train import create_train_state, make_train_step

    model = Transformer(compute_dtype=jnp.bfloat16, attn_impl="flash",
                        remat=True, **MODEL)
    tx = optax.adamw(3e-4)
    tokens = jax.ShapeDtypeStruct((FULL.batch, FULL.seq), jnp.int32,
                                  sharding=one_chip)
    state = jax.eval_shape(
        lambda t: create_train_state(model, jax.random.PRNGKey(0), t, tx)[0],
        tokens)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    step = make_train_step(model, tx, **step_kw)
    return step.lower(_on(one_chip, state), tokens, tokens,
                      _on(one_chip, key)).compile()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_train_step_has_its_kernels_and_fits(one_chip, as_on_chip):
    compiled = _train_program(one_chip)
    # 48: per layer the forward, its remat recompute, dQ, dK/dV
    assert compiled.as_text().count(KERNEL) == FULL.train_kernels
    assert _device_bytes(compiled) < HBM_BYTES


def test_cross_host_train_step_bucketed(one_chip, as_on_chip):
    from conftest import free_port

    from tpunet import distributed

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        compiled = _train_program(one_chip, cross_host=True,
                                  bucket_bytes=64 << 20)
    finally:
        distributed.finalize()
    assert compiled.as_text().count(KERNEL) == FULL.train_kernels
    assert _device_bytes(compiled) < HBM_BYTES


def test_cross_host_train_step_is_two_programs_without_a_host_transfer(
        one_chip, as_on_chip):
    """The flat cross-host step: the gradient leaves the chip between its two
    programs, as a tuple of chunks with static shapes, so neither program
    holds a callback, a send or a recv, and the kernels are all in the
    first."""
    from conftest import free_port

    from tpunet import distributed, interop

    distributed.finalize()
    distributed.initialize(f"127.0.0.1:{free_port()}", 0, 1)
    try:
        compiled = _train_program(one_chip, cross_host=True)
    finally:
        distributed.finalize()
    text = compiled.as_text()
    # 2.9 GB of f32 gradient: whole chunks of the shipped size and a last one
    chunks = compiled._grad.output_shardings[1]
    per = interop._CHUNK_BYTES // 4
    assert len(chunks) > 2 and text.count(f"f32[{per}]") >= 2 * (len(chunks) - 1)
    assert compiled._grad.as_text().count(KERNEL) == FULL.train_kernels
    assert text.count(KERNEL) == FULL.train_kernels
    assert "jit_grad_program" in text and "jit_apply_program" in text
    for mark in ("is_host_transfer", "callback", "send-done", "recv-done"):
        assert mark not in text, mark


@pytest.mark.parametrize("kv_heads,window", [(None, None), (4, 256)],
                         ids=["mha", "gqa4_window256"])
def test_generate_b8_p512_n256(one_chip, as_on_chip, kv_heads, window):
    from tpunet.models import Transformer, generate

    model = Transformer(compute_dtype=jnp.bfloat16, attn_impl="flash",
                        n_kv_heads=kv_heads, attn_window=window, **MODEL)
    prompt = jax.ShapeDtypeStruct((FULL.dec_batch, FULL.prompt), jnp.int32,
                                  sharding=one_chip)
    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"], prompt)
    compiled = jax.jit(lambda p, t: generate(model, p, t, FULL.new)).lower(
        _on(one_chip, params), prompt).compile()
    assert compiled.as_text().count(KERNEL) == FULL.decode_kernels  # the prefill
