"""Jitted training step with mesh shardings + optional cross-host gradient
sync over the tpunet DCN transport.

Design (TPU-first):
  * On one host, ONE jitted function contains forward, backward, and update
    — XLA fuses elementwise ops into the matmuls and inserts ICI collectives
    from the array shardings (batch over `dp`, Megatron-split classifier
    over `mdl`).
  * Cross-host gradient sync all-reduces the whole gradient pytree as ONE
    contiguous vector (the leaves raveled in order), so the
    multi-stream transport stripes a single large message instead of
    dribbling per-layer buffers — the same bucketing insight behind the
    reference's fairness design (large chunked messages saturate parallel
    streams; reference SURVEY §2.2 step 5).
  * That all-reduce sits between backward and the optimizer, which is a
    program boundary: the flat cross-host step is TWO device programs (grad,
    apply) with the ring between them on the host, the vector crossing by
    the runtime's ordinary array transfers (`_BoundaryStep`), in chunks
    large enough to stripe (tens of MiB) whose ways out, rings and ways
    back overlap (tpunet.interop.host_all_reduce). Inside one
    program it would cross as an `io_callback` host transfer, which on the
    v5e cost 2.7 s of a 3.15 s VGG16 step around a 0.29 s ring (PERF.md,
    PR 24 and 25). Collectives in the MIDDLE of a program (ZeRO's
    reduce-scatter and all-gather, the bucketed overlap, ring attention)
    have no boundary to stand at and keep tpunet.interop's in-jit seam.
"""

from __future__ import annotations

import math
import re
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def create_train_state(model, rng, sample_input, tx) -> tuple[TrainState, Any]:
    """Initialize params + optimizer state. Returns (state, apply_fn)."""
    params = model.init(rng, sample_input)["params"]
    opt_state = tx.init(params)
    return TrainState(params, opt_state, jnp.zeros((), jnp.int32)), model.apply


def _backward_order_key(path_str: str):
    """Sort key approximating backward completion order: output-side layers
    (lm_head, final norm) first, transformer blocks in descending index,
    embeddings last. A scheduling HINT only — each bucket's start callback
    fires once its gradients exist, so matching the backward order maximizes
    compute/transfer overlap, but correctness never depends on it."""
    m = re.search(r"block(\d+)", path_str)
    if m:
        return (1, -int(m.group(1)), path_str)
    if "embed" in path_str:
        return (2, 0, path_str)
    return (0, 0, path_str)


def _bucketed_dcn_pmean(grads, bucket_bytes: int, compression: str | None, world: int):
    """Mean-all-reduce the gradient pytree over DCN in byte-bounded buckets,
    nonblocking: every bucket's reduction is SUBMITTED (dcn_all_reduce_start)
    before any is WAITED (dcn_all_reduce_finish), so the native worker thread
    reduces bucket k while XLA still computes the gradients feeding bucket
    k+1 — the overlap that produced the reference's end-to-end VGG16 win
    (reference README.md:52-84; request depth per cc/nccl_types.h:50)."""
    from tpunet.interop import dcn_all_reduce_finish, dcn_all_reduce_start

    leaves_with_path = jax.tree_util.tree_leaves_with_path(grads)
    treedef = jax.tree_util.tree_structure(grads)
    # float0 leaves (frozen integer params under allow_int — QLoRA's int8
    # base) carry no gradient to reduce and cannot be concatenated; they
    # pass straight through to the reconstruction below.
    reducible = [i for i, (_, leaf) in enumerate(leaves_with_path)
                 if leaf.dtype != jax.dtypes.float0]
    order = sorted(
        reducible,
        key=lambda i: _backward_order_key(jax.tree_util.keystr(leaves_with_path[i][0])),
    )

    # Greedy byte-bounded buckets in backward order; same-dtype within a
    # bucket (they concatenate into one flat vector).
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i in order:
        leaf = leaves_with_path[i][1]
        nb = leaf.size * leaf.dtype.itemsize
        if cur and (
            cur_bytes + nb > bucket_bytes
            or leaf.dtype != leaves_with_path[cur[0]][1].dtype
        ):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)

    # Phase 1: submit every bucket. Phase 2: collect. The ordered-callback
    # token chain keeps submission order identical on all ranks.
    flats, tickets = [], []
    for b in buckets:
        flat = jnp.concatenate([leaves_with_path[i][1].reshape(-1) for i in b])
        if compression == "bf16":
            flat = flat.astype(jnp.bfloat16)
        tickets.append(dcn_all_reduce_start(flat))
        flats.append(flat)

    new_leaves: list[Any] = [leaf if leaf.dtype == jax.dtypes.float0
                             else None
                             for _, leaf in leaves_with_path]
    for b, flat, ticket in zip(buckets, flats, tickets):
        reduced = dcn_all_reduce_finish(ticket, flat)
        off = 0
        for i in b:
            leaf = leaves_with_path[i][1]
            seg = reduced[off : off + leaf.size].astype(leaf.dtype)
            new_leaves[i] = seg.reshape(leaf.shape) / world
            off += leaf.size
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _wire_handles_bf16() -> bool:
    """True when the native communicator already compresses f32 payloads to
    bf16 ON THE WIRE (wire_dtype="bf16" / TPUNET_WIRE_DTYPE=bf16 — see
    docs/DESIGN.md "Compressed collectives"). The trainer then ships f32
    gradients straight through — ONE cast path, at the wire hop, with f32
    accumulation inside the ring — instead of double-casting in JAX and
    reducing in bf16. Communicators without the codec (f32-wire, or an
    emulated backend without a wire_dtype at all) keep the pure-Python
    bf16 cast."""
    from tpunet import distributed

    if not distributed.is_initialized():
        return False
    return getattr(distributed.global_communicator(), "wire_dtype", "f32") == "bf16"


def multi_head_targets(labels, n_heads: int):
    """Targets of `n_heads` prediction heads from next-token labels (.., s):
    head j at position t is asked for labels[t + j] (the token at t + 1 + j).
    Returns (targets (.., s, n_heads), inside (s, n_heads)): inside is False
    where t + j falls past the sequence, and the target there is a filler."""
    s = labels.shape[-1]
    at = jnp.arange(s)[:, None] + jnp.arange(n_heads)[None, :]
    return jnp.take(labels, jnp.minimum(at, s - 1), axis=-1), at < s


def _sown(mut, name: str) -> list:
    """The values sown under `name`, one a layer that sowed it."""
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
        mut.get("intermediates", {}))
        if any(getattr(k, "key", None) == name for k in path)]


def _make_loss_fn(model, images, labels, dropout_rng, moe_aux_weight: float,
                  fused_xent_block: int | None = None,
                  z_loss: float = 0.0):
    """The train-step objective, shared by the replicated and ZeRO paths:
    token/label cross-entropy plus (for MoE models) the Switch router's sown
    load-balancing losses, collected via mutable=['intermediates'] — without
    that term the router can collapse onto one expert.

    fused_xent_block: compute the cross-entropy blockwise over the vocab
    (tpunet.ops.blockwise_cross_entropy) so the (batch, seq, vocab) logits
    are never materialized — requires a model supporting
    ``features_only=True`` (the Transformer family) whose lm head lives at
    params['lm_head']['kernel']. KNOWN LIMIT: the fused path reads the head
    kernel directly, so under Megatron TP (lm_head split over tp_axis) GSPMD
    gathers the kernel and replicates the head compute — numerically fine,
    but the head's TP speedup is lost; prefer the default path when the lm
    head is tensor-parallel."""
    has_moe = getattr(model, "n_experts", 0) > 0
    # a model whose attention selects its keys sows its indexer's loss
    has_index = getattr(model, "attn_select_top_k", None) is not None
    # a model with a multi-token-prediction module sows that module's loss
    has_mtp = bool(getattr(model, "mtp_pattern", None))
    sows = has_moe or has_index or has_mtp
    if fused_xent_block is not None and getattr(model, "tp_axis", None):
        import warnings

        warnings.warn(
            "fused_xent_block with a tensor-parallel lm head replicates the "
            "head compute (kernel is gathered); the TP head speedup is lost",
            stacklevel=3,
        )

    fused = fused_xent_block is not None
    mean = jnp.mean
    if getattr(model, "n_pred_heads", 1) > 1:
        # Several prediction heads: logits (b, s, heads, vocab); the loss is
        # the mean over the (position, head) pairs whose target lies inside
        # the sequence.
        if fused:
            raise ValueError("fused_xent_block reads one position's logits "
                             "from the head; the model has n_pred_heads > 1")
        labels, inside = multi_head_targets(labels, model.n_pred_heads)
        share = inside / (jnp.sum(inside) * math.prod(labels.shape[:-2]))
        mean = lambda x: jnp.sum(x * share.astype(x.dtype))  # noqa: E731

    def loss_fn(p):
        out = model.apply(
            {"params": p}, images, train=True, rngs={"dropout": dropout_rng},
            mutable=["intermediates"] if sows else False,
            **({"features_only": True} if fused else {}),
        )
        out, mut = out if sows else (out, None)
        if fused:
            from tpunet.ops import blockwise_cross_entropy

            nll, lse = blockwise_cross_entropy(
                out.reshape(-1, out.shape[-1]),
                p["lm_head"]["kernel"],
                labels.reshape(-1),
                block_vocab=fused_xent_block,
                return_lse=True,
            )
            loss = nll.mean()
            if z_loss:
                loss = loss + z_loss * jnp.mean(jnp.square(lse))
        elif z_loss:
            # Single pass over the logits IN THEIR OWN DTYPE: lse feeds
            # BOTH the nll (lse - picked, optax's own identity, same dtype
            # semantics as the z=0 branch) and the z term — no second
            # logsumexp, no upcast copy of the logits tensor.
            lse = jax.scipy.special.logsumexp(out, axis=-1)
            picked = jnp.take_along_axis(
                out, labels[..., None], axis=-1)[..., 0]
            loss = mean(lse - picked) + z_loss * mean(
                jnp.square(lse.astype(jnp.float32)))
        else:
            loss = mean(optax.softmax_cross_entropy_with_integer_labels(out, labels))
        if has_moe:
            # flax wraps sown values in tuples: sum leaves on matching paths
            # and average over MoE blocks.
            aux = _sown(mut, "moe_aux_loss")
            if aux:
                loss = loss + moe_aux_weight * (sum(aux) / len(aux)).astype(loss.dtype)
        if has_index:
            # the indexer's own loss, the mean over the layers that sow one,
            # at the model's weight; its gradient reaches the indexers alone
            index = _sown(mut, "dsa_index_loss")
            loss = loss + model.index_loss_weight * (
                sum(index) / len(index)).astype(loss.dtype)
        if has_mtp:
            mtp = _sown(mut, "mtp_loss")
            loss = loss + model.mtp_loss_weight * (sum(mtp) / len(mtp)).astype(loss.dtype)
        return loss

    return loss_fn


def _grad_zeros(p):
    """Zero gradient accumulator for one param leaf: ordinary zeros for
    inexact dtypes, a float0 placeholder for integer leaves (QLoRA's
    frozen int8 base) — float0 is what allow_int gradients produce, and
    it never accumulates or divides."""
    import numpy as np

    if jnp.issubdtype(p.dtype, jnp.inexact):
        return jnp.zeros_like(p)
    return np.zeros(p.shape, jax.dtypes.float0)


def _grad_add(acc, g):
    return acc if acc.dtype == jax.dtypes.float0 else jnp.add(acc, g)


def _apply_updates(params, updates):
    """optax.apply_updates with float0 pass-through: a float0 update
    (integer leaf under allow_int — QLoRA's frozen int8 base) leaves the
    leaf untouched; fp updates apply with the usual cast back to the
    param dtype."""
    return jax.tree.map(
        lambda p, u: p if u.dtype == jax.dtypes.float0
        else jnp.asarray(p + u, p.dtype), params, updates)


def _value_and_grads(model, params, images, labels, dropout_rng,
                     moe_aux_weight: float, fused_xent_block: int | None,
                     accum_steps: int | None, z_loss: float = 0.0):
    """(mean loss, mean grads) for the batch — in one backward, or (with
    accum_steps=k) as a lax.scan over k microbatches whose activations are
    freed between iterations: the throughput-neutral way to run a batch k×
    larger than activation memory allows. For dense models equal
    microbatches make the mean-of-means exactly the full-batch mean; MoE
    models route and compute expert capacity PER MICROBATCH (capacity =
    f(micro tokens), aux loss is batch-nonlinear), the standard practice but
    a slightly different objective than one full-batch step."""
    if accum_steps is None or accum_steps == 1:
        loss_fn = _make_loss_fn(model, images, labels, dropout_rng,
                                moe_aux_weight, fused_xent_block, z_loss)
        # allow_int: identical for ordinary fp trees, and lets a QLoRA
        # tree (frozen int8 base leaves inside params) differentiate —
        # the int leaves come back as float0, which _apply_updates and
        # the float0-aware accumulation below treat as "frozen".
        return jax.value_and_grad(loss_fn, allow_int=True)(params)

    batch = images.shape[0]
    if batch % accum_steps != 0:
        raise ValueError(f"batch {batch} not divisible by accum_steps {accum_steps}")
    micro = batch // accum_steps
    # STRIDED microbatches (row r -> microbatch r % k), not contiguous
    # blocks: under a dp-sharded batch axis, contiguous blocks would put a
    # whole microbatch on a subset of dp ranks (idling the rest each scan
    # step), while strided grouping keeps every rank's shard contributing
    # rows to every microbatch. Any equal-size grouping preserves the
    # mean-of-means identity, so numerics don't care.
    images_mb = images.reshape(micro, accum_steps, *images.shape[1:]).swapaxes(0, 1)
    labels_mb = labels.reshape(micro, accum_steps, *labels.shape[1:]).swapaxes(0, 1)
    keys = jax.random.split(dropout_rng, accum_steps)

    def body(carry, xs):
        loss_sum, grad_sum = carry
        im, lb, key = xs
        loss_fn = _make_loss_fn(model, im, lb, key, moe_aux_weight,
                                fused_xent_block, z_loss)
        loss, grads = jax.value_and_grad(loss_fn, allow_int=True)(params)
        return (loss_sum + loss,
                jax.tree.map(_grad_add, grad_sum, grads)), None

    init = (jnp.zeros((), jnp.float32), jax.tree.map(_grad_zeros, params))
    (loss_sum, grad_sum), _ = jax.lax.scan(body, init, (images_mb, labels_mb, keys))
    return loss_sum / accum_steps, jax.tree.map(
        lambda g: g if g.dtype == jax.dtypes.float0 else g / accum_steps,
        grad_sum
    )


def make_train_step(model, tx, cross_host: bool = False, donate: bool = True,
                    grad_compression: str | None = None,
                    moe_aux_weight: float = 0.01,
                    bucket_bytes: int | None = None,
                    fused_xent_block: int | None = None,
                    accum_steps: int | None = None,
                    z_loss: float = 0.0):
    """Build the train step: (state, inputs, labels, rng) -> (state, loss).

    cross_host=False (one host): ONE jitted program, `state` donated when
    `donate`. The return value is the `jax.jit` object itself.

    cross_host=True adds the DCN gradient all-reduce tier (requires
    tpunet.distributed.initialize() BEFORE this call — the world size is
    baked into the executables). The step is then two jitted programs and
    the exchange between them (`_BoundaryStep`, called like the jitted step;
    `.lower(*args).compile()` compiles both ahead of time, `.as_text()` of
    the result is both programs' text):
      1. grad: forward and backward, then the contiguous chunks of the
         gradient's flat vector (static shapes, from its bytes and the world
         size: interop.boundary_chunks; a vector under one chunk stays
         whole), each joined from slices of the leaves it overlaps: the
         vector itself is never formed (_boundary_cut). Returns (loss,
         chunks); nothing is donated: the apply program still needs `state`.
      2. on the host, tpunet.interop.host_all_reduce(chunks): the chunks'
         copies to the host run a few ahead, a
         Communicator.all_reduce(sum) a chunk over the
         process-default communicator as it is at that call (elastic
         recovery re-points it under compiled programs) into a result
         buffer the step object keeps across steps, and back to the
         device: chunk k on its way back while chunk k+1 is in the ring
         and the ones after it still on their way out.
      3. apply: the reduced chunks joined (on the CPU backend the vector
         comes back whole: interop.reduced_like), the mean (the sum over
         `world`, on the device, in the vector's dtype), unraveled, through
         `tx` into the parameters; `state` donated when `donate`. The call
         returns when this program has finished (the kept buffer is then
         free again).
    The all-reduce sits at the boundary because it can: a collective between
    backward and the optimizer needs nothing of either program while it
    runs, and outside a program its bytes move by the runtime's plain array
    transfers instead of an `io_callback`'s host-transfer operations.

    grad_compression="bf16" casts the gradient's chunks to bfloat16
    before the cross-host all-reduce and back after — halving DCN bytes for
    ~1 ulp of bf16 noise on already-noisy SGD gradients (the reference has
    no compression; its parent project's QAdam/bytegrad live a layer above —
    this is that capability at the transport-facing tier).

    When the model has MoE blocks (``n_experts > 0``), the Switch router's
    sown load-balancing losses are collected via mutable=['intermediates']
    and added to the loss scaled by ``moe_aux_weight`` — without this term
    the router can collapse onto one expert and capacity-drop most tokens.

    bucket_bytes (cross_host only): sync gradients in byte-bounded buckets
    via NONBLOCKING all-reduces INSIDE one jitted program instead of one
    flat vector at the boundary, so DCN transfer overlaps backward compute
    (see _bucketed_dcn_pmean; the in-jit io_callback seam). None keeps the
    single-vector path.
    """
    if grad_compression not in (None, "bf16"):
        raise ValueError(f"unknown grad_compression {grad_compression!r}")
    if bucket_bytes is not None and not cross_host:
        raise ValueError("bucket_bytes requires cross_host=True")
    if cross_host:
        # Import here so single-host training never touches the transport.
        from tpunet import distributed

        world = distributed.world_size()  # raises early if initialize() was skipped
        # One cast path: when the wire already compresses to bf16, ship f32
        # gradients and let the ring quantize at the hops (f32 accumulation;
        # strictly better numerics than reducing in bf16). Decided at trace
        # time like every other cross-host choice.
        if grad_compression == "bf16" and _wire_handles_bf16():
            grad_compression = None

    def value_and_grads(state, images, labels, dropout_rng):
        return _value_and_grads(model, state.params, images, labels,
                                dropout_rng, moe_aux_weight,
                                fused_xent_block, accum_steps, z_loss)

    def updated(state, grads):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = _apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1)

    donated = (0,) if donate else ()
    if not cross_host or bucket_bytes is not None:
        def train_step(state: TrainState, images, labels, dropout_rng):
            loss, grads = value_and_grads(state, images, labels, dropout_rng)
            if cross_host:
                grads = _bucketed_dcn_pmean(grads, bucket_bytes, grad_compression, world)
            return updated(state, grads), loss

        return jax.jit(train_step, donate_argnums=donated)

    def grad_program(state: TrainState, images, labels, dropout_rng):
        loss, grads = value_and_grads(state, images, labels, dropout_rng)
        # float0 leaves (QLoRA's frozen int8 base under allow_int) carry no
        # gradient and stay behind.
        return loss, _boundary_cut([g for g in jax.tree.leaves(grads)
                                    if g.dtype != jax.dtypes.float0],
                                   grad_compression, world)

    def apply_program(state: TrainState, reduced):
        # A gradient has its parameter's shape and dtype, and an integer
        # parameter's is float0: the tree the vector was raveled from is
        # read off the parameters (whose own raveled values nothing uses).
        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        f0 = [not jnp.issubdtype(p.dtype, jnp.inexact) for p in leaves]
        like, unravel = ravel_pytree(
            [p for p, skip in zip(leaves, f0) if not skip])
        reduced = jnp.concatenate(reduced)
        # the mean in the wire's dtype, as dcn_pmean forms it
        mean = reduced / jnp.asarray(world, reduced.dtype)
        it = iter(unravel(mean.astype(like.dtype)))
        grads = jax.tree_util.tree_unflatten(
            treedef, [_grad_zeros(p) if skip else next(it)
                      for p, skip in zip(leaves, f0)])
        return updated(state, grads)

    # The reduced chunks are not donated: no output has their shape, so XLA
    # could not reuse them, and they are dropped when the call returns anyway.
    return _BoundaryStep(jax.jit(grad_program),
                         jax.jit(apply_program, donate_argnums=donated))


def _boundary_cut(leaves, compression: str | None, world: int) -> tuple:
    """The chunks that jnp.split(ravel_pytree(leaves)[0]) at
    interop.boundary_chunks gives (the leaves' common dtype, bfloat16 under
    compression), each joined from slices of the leaves it overlaps: the
    whole vector is never formed. Formed and split, it cost XLA 0.89 GB of
    temporaries a VGG16 step wherever a chunk spans the end of one large
    leaf and the start of the next (PERF.md, PR 31 (2) and PR 34)."""
    from tpunet.interop import boundary_chunks

    # ravel_pytree's dtype: the leaves' dtypes promoted, weak types aside
    dtype = jnp.result_type(*(leaf.dtype for leaf in leaves))
    wire = jnp.dtype(jnp.bfloat16 if compression == "bf16" else dtype)
    starts = np.cumsum([0] + [leaf.size for leaf in leaves]).tolist()
    chunks, a = [], 0
    for size in boundary_chunks(starts[-1], wire.itemsize, world):
        b, pieces = a + size, []
        for leaf, start, end in zip(leaves, starts, starts[1:]):
            lo, hi = max(a, start), min(b, end)
            if lo < hi:
                piece = leaf.reshape(-1)[lo - start:hi - start]
                pieces.append(piece.astype(dtype).astype(wire))
        chunks.append(pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces))
        a = b
    return tuple(chunks)


class _BoundaryStep:
    """The flat cross-host step: a grad program and an apply program with
    the gradient's all-reduce between them, on the host. Called like the
    jitted step it stands in for; `.lower(*args).compile()` compiles both
    halves ahead of time and gives an object of the same kind whose
    `.as_text()` is both programs' text.

    The ring's result buffer lives here, across steps (`_out`, made at the
    first call, the whole vector's; every chunk's ring writes its own slice
    of it): a new one a step costs its page faults, 0.6 s for VGG16's 553 MB
    on the v5e's host, on the chip rank and on a CPU rank alike. The way out
    pays the same for the blocks the runtime allocates a step (a chunk's
    landing on an accelerator's rank, the grad program's chunk outputs on a
    CPU rank), which this object cannot keep: building one tells the
    allocator to (interop.retain_freed_host_blocks). Each slice
    is handed to jax.device_put, which returns before an accelerator has
    the bytes and which on the CPU backend aliases it: so a call returns
    only when its apply program, which reads all the slices, has finished,
    and the next call's first ring finds every slice free whatever state
    that call is given. Within a call a slice is written once, before its
    device_put."""

    def __init__(self, grad, apply):
        from tpunet.interop import retain_freed_host_blocks

        retain_freed_host_blocks()
        self._grad, self._apply = grad, apply
        self._out = None

    def __call__(self, state, images, labels, dropout_rng):
        from tpunet.interop import host_all_reduce, host_buffer_like

        loss, chunks = self._grad(state, images, labels, dropout_rng)
        if self._out is None:
            self._out = host_buffer_like(jax.ShapeDtypeStruct(
                (sum(c.size for c in chunks),), chunks[0].dtype))
        state = self._apply(state, host_all_reduce(chunks, self._out))
        jax.block_until_ready(state.step)
        return state, loss

    def lower(self, state, images, labels, dropout_rng):
        return _LoweredBoundaryStep(
            self._grad.lower(state, images, labels, dropout_rng),
            self._apply, state)

    def as_text(self) -> str:
        return self._grad.as_text() + "\n" + self._apply.as_text()


class _LoweredBoundaryStep:
    """The apply program's operands are placed where the grad program leaves
    its chunks, which only the compiled grad program says: so the second
    half is lowered here, in compile()."""

    def __init__(self, grad_lowered, apply, state):
        self._grad, self._apply, self._state = grad_lowered, apply, state

    def compile(self) -> _BoundaryStep:
        from tpunet.interop import reduced_like

        grad = self._grad.compile()
        reduced = reduced_like(self._grad.out_info[1], grad.output_shardings[1])
        return _BoundaryStep(grad, self._apply.lower(self._state, reduced).compile())


def _zero_shard_geometry(n: int, world: int) -> tuple[int, int]:
    """(padded_size, shard_size) for an n-element flat vector over `world`
    equal shards."""
    pad = (-n) % world
    return n + pad, (n + pad) // world


def create_zero_train_state(model, rng, sample_input, tx) -> tuple[TrainState, Any]:
    """ZeRO-1 companion to create_train_state: the optimizer state is built
    on THIS RANK's flat parameter shard (1/world of the elements), not the
    full pytree — the memory that dominates adamw training (2 f32 moments
    per parameter) shrinks by the DCN world size. Requires
    tpunet.distributed.initialize() first; every rank must call it."""
    from tpunet import distributed

    world = distributed.world_size()
    rank = distributed.rank()
    params = model.init(rng, sample_input)["params"]
    flat, _ = ravel_pytree(params)
    padded, shard_n = _zero_shard_geometry(flat.size, world)
    if padded != flat.size:
        flat = jnp.concatenate([flat, jnp.zeros(padded - flat.size, flat.dtype)])
    shard = jax.lax.dynamic_slice(flat, (rank * shard_n,), (shard_n,))
    return TrainState(params, tx.init(shard), jnp.zeros((), jnp.int32)), model.apply


def make_zero_train_step(model, tx, donate: bool = True,
                         grad_compression: str | None = None,
                         moe_aux_weight: float = 0.01,
                         fused_xent_block: int | None = None,
                         accum_steps: int | None = None,
                         z_loss: float = 0.0):
    """ZeRO-1 (optimizer-state sharding) cross-host train step.

    Instead of all-reducing the full gradient and updating replicated
    optimizer state (make_train_step cross_host=True), each step:
      1. reduce-scatters the flat gradient over DCN — each rank receives the
         MEAN of its 1/world shard (same wire bytes as ring all-reduce's RS
         phase; the reference's parent project ships sharded optimizers a
         layer above its transport — this is that capability here),
      2. applies `tx` to the shard against the matching parameter shard
         (update FLOPs and optimizer memory both /world),
      3. all-gathers the updated parameter shards (the AG phase's bytes).
    Total DCN traffic equals the all-reduce path; memory and update compute
    drop by world. The trajectory matches the replicated path to float
    rounding: the ring all-reduce computes each element's sum in exactly the
    RS phase this path runs, and adamw/sgd are elementwise, so sharding the
    vector does not reorder any per-element arithmetic.

    State must come from create_zero_train_state (sharded opt_state).
    grad_compression="bf16" halves the reduce-scatter bytes (the gather of
    updated params stays full precision).

    Elastic caveat: the opt-state shard geometry bakes in (rank, world) at
    trace time, so after an elastic rebuild that CHANGES the world size
    (allow_shrink) the sharded opt state is invalid — rebuild it with
    create_zero_train_state and restore params (not opt state) from the
    checkpoint. Fixed-world rebuilds (replacement policy) resume fine.
    """
    if grad_compression not in (None, "bf16"):
        raise ValueError(f"unknown grad_compression {grad_compression!r}")
    from tpunet import distributed
    from tpunet.interop import dcn_all_gather, dcn_reduce_scatter

    world = distributed.world_size()
    rank = distributed.rank()
    # One cast path (see make_train_step): the native wire codec quantizes
    # the reduce-scatter's hops itself, with f32 accumulation.
    if grad_compression == "bf16" and _wire_handles_bf16():
        grad_compression = None

    def train_step(state: TrainState, images, labels, dropout_rng):
        loss, grads = _value_and_grads(model, state.params, images, labels,
                                       dropout_rng, moe_aux_weight,
                                       fused_xent_block, accum_steps, z_loss)

        gflat, _ = ravel_pytree(grads)
        pflat, unravel = ravel_pytree(state.params)
        n = pflat.size
        padded, shard_n = _zero_shard_geometry(n, world)
        if padded != n:
            zpad = jnp.zeros(padded - n, gflat.dtype)
            gflat = jnp.concatenate([gflat, zpad])
            pflat = jnp.concatenate([pflat, zpad.astype(pflat.dtype)])

        if grad_compression == "bf16":
            gshard = dcn_reduce_scatter(gflat.astype(jnp.bfloat16))
            gshard = gshard.astype(gflat.dtype) / world
        else:
            gshard = dcn_reduce_scatter(gflat) / world
        pshard = jax.lax.dynamic_slice(pflat, (rank * shard_n,), (shard_n,))

        updates, opt_state = tx.update(gshard, state.opt_state, pshard)
        new_pshard = optax.apply_updates(pshard, updates)

        gathered = dcn_all_gather(new_pshard).reshape(-1)[:n]
        params = unravel(gathered)
        return TrainState(params, opt_state, state.step + 1), loss

    return jax.jit(train_step, donate_argnums=(0,) if donate else ())


def synthetic_batch(rng: np.random.Generator, batch: int, image_size: int,
                    num_classes: int, channels: int = 3):
    """Random NHWC images + integer labels (the synthetic-benchmark diet)."""
    images = rng.standard_normal((batch, image_size, image_size, channels)).astype(np.float32)
    labels = rng.integers(0, num_classes, size=(batch,)).astype(np.int32)
    return images, labels
