"""Continuous batching — a slot server over the per-row decode cache.

`generate()` advances one batch in lockstep: every sequence prefills
together and the call returns when the LAST one finishes, so short
requests wait on long ones and finished rows burn MXU cycles. The
`BatchServer` removes both: the model runs with `per_row_cache=True`
(each batch row carries its own `cache_index`), so rows are independent
sequences — a finished row's slot is re-prefilled for the next queued
request while the other rows keep decoding, and nothing ever waits.

TPU-first shape discipline: the decode step is ONE jitted program of
static shape (slots, 1) regardless of which slots are live — occupancy
changes never recompile. Slot refill is a second jitted program per
distinct prompt length (row slice → reset index → kernel-routed prefill
→ row write-back); bucket or pad prompts to a few lengths to bound
retraces, exactly like any static-shape serving stack. Idle rows decode
garbage tokens into their own dead cache rows — per-row masking keeps
them from touching live rows, a refill resets the row's index to 0, and
the stale K/V above the new sequence's frontier is masked until
overwritten (`key_pos <= q_pos`, the same argument that makes
speculative rollback sound).

Speculative mode (`draft_model=`): each decode window becomes
`steps_per_call` SPECULATIVE ROUNDS — draft gamma tokens per slot, verify
in one target forward, commit each row's own accepted prefix plus the
fix/bonus token (same exactness machinery as `speculative_generate`:
shared filtered distribution, residual sampling, ring stash/restore).
A dispatch then commits up to gamma+1 tokens per row instead of one;
greedy outputs are bitwise `generate()`'s. The draft cache rides the same
slot lifecycle (row surgery prefills both).

Disaggregated mode (`submit_kv()`): a request whose prompt K/V was
computed on a PREFILL RANK and shipped over the transport (tpunet.serve)
refills its slot through a jitted adopt program — shipped prefix written
into the row, index set, first token sampled from the shipped logits —
instead of re-running prefill. On an exact (f32) KV wire the adopted state
is bitwise what local prefill would have produced, so greedy outputs
cannot be told apart from single-host serving (docs/DESIGN.md §10).

The reference repo has no inference path at all (it is a transport;
SURVEY §2.3); this is framework capability above it.
"""

from __future__ import annotations

from collections import deque
from functools import partial
from itertools import count

import jax
import jax.numpy as jnp
import numpy as np

from tpunet.models.generate import (_get_cache_index, _kv_leaves,
                                    _make_spec_round_core, _map_cache_index,
                                    _prefill, _set_cache_index, _spec_ring_ok,
                                    _validate_sampling, filtered_logits,
                                    init_cache, make_sampler)


def _clamp_cache_index(cache, cap):
    """Clamp every cache_index leaf to cap. Idle (freed, not-yet-refilled)
    slots keep decoding garbage every window and their per-row index would
    otherwise grow without bound — int32-wrapping after ~2^31 idle steps
    and leaning on scatter out-of-bounds drop semantics for an unbounded
    range of positions. Clamped, an idle row's index parks at cap: its
    (single, constant) write position cap is one-past-end (dropped), the
    overflow NaN-poison still marks the row's output as garbage, and a
    refill resets the index anyway. Live rows are unaffected — submit()
    bounds prompt + max_new <= max_len, so a live row's index never
    exceeds cap."""
    return _map_cache_index(cache, lambda leaf: jnp.minimum(leaf, cap))


class BatchServer:
    """Continuous-batching decode server.

    submit() enqueues a request; slots are assigned at the next
    step()/run() boundary, so a burst of submissions prefills as one
    batched dispatch. step() advances every live slot one token (or one
    speculative ROUND of up to gamma+1 tokens when a draft_model is
    given) and returns the requests that finished. Greedy by default;
    temperature/top-k/top-p sample per-row from the device-carried key
    chain.
    """

    def __init__(self, model, params, *, slots: int, max_len: int,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None, eos_id: int | None = None,
                 rng=None, prefill_chunk: int | None = None,
                 steps_per_call: int = 1, refill_coalesce: int = 1,
                 draft_model=None, draft_params=None, gamma: int = 4,
                 on_first_token=None):
        _validate_sampling(temperature, top_k, top_p)
        if (draft_model is None) != (draft_params is None):
            raise ValueError("draft_model and draft_params come together")
        if draft_model is not None and gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if draft_model is not None and getattr(draft_model, "n_experts", 0):
            raise ValueError("draft_model must be dense (same MoE "
                             "batch-coupling argument as the target)")
        if (draft_model is not None
                and draft_model.vocab != model.vocab):
            raise ValueError("draft vocab must match the target")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {steps_per_call}")
        if refill_coalesce < 1:
            raise ValueError(
                f"refill_coalesce must be >= 1, got {refill_coalesce}")
        if getattr(model, "n_experts", 0):
            # MoE capacity is computed batch-wide (t = b*s slots claimed by
            # a cross-row cumsum), so other rows' tokens - including idle
            # garbage - change which of a live row's tokens get dropped:
            # the per-slot parity contract cannot hold. Reject loudly.
            raise ValueError(
                "BatchServer requires a dense model: MoE capacity couples "
                "rows (batch-wide expert slots), breaking per-slot "
                "independence")
        self.model = model
        self.params = params
        self.slots, self.max_len = slots, max_len
        # Refill batching: a freed slot is NOT refilled until at least
        # this many slots are free (or nothing is decoding, or the queue
        # would drain anyway). Singleton (1, p) prefills waste matmul
        # width (measured at d256: 12 singles ~100 ms vs 4 batched (4, p)
        # ~53 ms), BUT holding a slot costs idle decode windows until a
        # partner frees, and when retirements are spread in time that
        # idleness exceeds the batching gain (measured: coalesce=2 LOST
        # 3-6% end-to-end on both toy and d256 configs). Default 1 =
        # refill immediately; raise it only when retirements cluster
        # (uniform max_new, bursty arrivals).
        self.refill_coalesce = min(refill_coalesce, slots)
        self.eos_id = eos_id
        self._sampling = (temperature, top_k, top_p)
        self._prefill_chunk = prefill_chunk
        self._dm = model.clone(
            decode=True, per_row_cache=True,
            decode_ring_cache=(_spec_ring_ok(model, gamma)
                               if draft_model is not None
                               else getattr(model, "decode_ring_cache",
                                            True)))
        # Speculative rounds overshoot the committed frontier by up to
        # gamma: the verify block must never cross the cache capacity for
        # a LIVE row, so spec mode adds gamma + 1 slack rows of K/V (the
        # submit() contract stays p + max_new <= max_len).
        cache_cap = max_len + (gamma + 1 if draft_model is not None else 0)
        self._cache = init_cache(self._dm, slots, cache_cap)
        self._draft = draft_model
        if draft_model is not None:
            self._dm_draft = draft_model.clone(
                decode=True, per_row_cache=True,
                decode_ring_cache=_spec_ring_ok(draft_model, gamma))
            self._dcache = init_cache(self._dm_draft, slots, cache_cap)
        self._free = list(range(slots))
        self._live: dict[int, dict] = {}       # slot -> request record
        self._pending: list[dict] = []
        self._ids = count()
        # Device-resident loop state: the per-slot last tokens and the rng
        # key live ON DEVICE and are donated through every jitted call —
        # the host never re-uploads them and never dispatches a bare
        # jax.random.split between steps. The only host<->device traffic
        # on the decode path is the one necessary window readback.
        self._toks = jnp.zeros(slots, jnp.int32)
        self._key = rng if rng is not None else jax.random.PRNGKey(0)
        self._done_buffer: list[dict] = []  # finished before step() drained
        self.stats = {"decode_windows": 0, "prefills": 0, "kv_adopts": 0}
        # Serving-tier hook: called with a request's id the moment its FIRST
        # token is committed (TTFT instrumentation for the disaggregated
        # decode worker). Host-side, after the window readback — never
        # inside a jitted program.
        self._on_first_token = on_first_token

        sample = make_sampler(temperature, top_k, top_p)

        # The cache is the dominant inference resident (slots x max_len x
        # layers); donating it keeps ONE buffer alive across the per-token
        # step instead of copy-in/copy-out each call (generate() gets this
        # for free by scanning inside one jit; the server's step is the
        # jit boundary). Donation is a no-op on CPU.
        #
        # steps_per_call > 1 scans that many micro-steps INSIDE the jit
        # (one dispatch + one host sync per window instead of per token) —
        # the lever that amortizes host-loop overhead at small step costs.
        # The scheduling granularity coarsens with it: retirements and
        # refills land at window boundaries, and a row that finishes
        # mid-window decodes garbage for the remainder (discarded; its
        # refill resets the row).
        max_len_cap = max_len

        # params are ARGUMENTS of every jitted program (bound below with
        # functools.partial, so callers keep the short signatures). A jit
        # that closes over them lowers each leaf as a literal constant of
        # the program: at the d2048 L12 model that is 2.9 GB of constants
        # compiled into every prefill shape and the decode step, each with
        # its own copy on the device.
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def decode_step(params_c, cache, toks, key):
            key, sub = jax.random.split(key)

            def body(carry, k):
                cache, tok = carry
                logits, mut = self._dm.apply(
                    {"params": params_c, "cache": cache}, tok[:, None],
                    mutable=["cache"])
                nxt = sample(logits[:, -1, :], k)
                return (mut["cache"], nxt), nxt

            (cache, toks), toks_out = jax.lax.scan(
                body, (cache, toks), jax.random.split(sub, steps_per_call))
            cache = _clamp_cache_index(cache, max_len_cap)
            # (slots, window) readback + the carried device state.
            return cache, toks, toks_out.swapaxes(0, 1), key

        @partial(jax.jit, donate_argnums=(1, 2), static_argnames=("chunk",))
        def prefill_slots(params_c, cache, toks, prompts, rows, key, chunk):
            # Row surgery, n rows at once: gather the claimed slots out of
            # every cache leaf, reset their indexes (the rows may hold
            # dead sequences' frontiers), prefill the (n, p) prompts
            # through the shared kernel-routed path, scatter the rows
            # back. One dispatch per same-length refill group.
            key, sub = jax.random.split(key)
            row = jax.tree.map(lambda a: a[rows], cache)
            row = _set_cache_index(row, 0)
            row, last = _prefill(self._dm, params_c, row, prompts, chunk)
            cache = jax.tree.map(
                lambda a, rw: a.at[rows].set(rw), cache, row)
            tok = sample(last, sub)  # (n,)
            toks = toks.at[rows].set(tok)
            return cache, toks, tok, key

        @partial(jax.jit, donate_argnums=(0, 1))
        def adopt_slots(cache, toks, kv, last, rows, key):
            # Disaggregated-serving refill: install SHIPPED prompt K/V into
            # the claimed slots instead of re-running prefill. `kv` is a
            # tuple of (n, p, kv_heads, head_dim) blocks in _kv_leaves
            # order (the prefill rank extracted them in the same order);
            # `last` is the prefill's final-position logits (n, vocab), so
            # the first token is sampled EXACTLY like the local-prefill
            # path (greedy outputs bitwise-equal to single-host serving on
            # an exact KV wire). Stale K/V above position p in the adopted
            # rows is masked by the decode step until overwritten — the
            # same argument that makes ordinary slot refill sound.
            key, sub = jax.random.split(key)
            plen = kv[0].shape[1]
            span = jnp.arange(plen)
            blocks = iter(kv)

            def fix(path, leaf):
                name = (path[-1].key if hasattr(path[-1], "key")
                        else str(path[-1]))
                if name in ("cached_key", "cached_value"):
                    blk = next(blocks).astype(leaf.dtype)
                    return leaf.at[rows[:, None], span[None, :]].set(blk)
                if name == "cache_index":
                    return leaf.at[rows].set(
                        jnp.asarray(plen, leaf.dtype))
                return leaf
            cache = jax.tree_util.tree_map_with_path(fix, cache)
            tok = sample(last, sub)  # (n,)
            toks = toks.at[rows].set(tok)
            return cache, toks, tok, key

        self._adopt_slots = adopt_slots

        if draft_model is not None:
            greedy = temperature == 0.0
            t_ring = _spec_ring_ok(model, gamma)
            d_ring = _spec_ring_ok(draft_model, gamma)
            spec_cap = max_len_cap + gamma + 1

            def probs_of(logits):
                return jax.nn.softmax(
                    filtered_logits(logits, temperature, top_k, top_p),
                    axis=-1)

            def spec_round(round_core, carry, key):
                # One speculative round over every slot (live or garbage):
                # draft gamma, verify in ONE target forward, commit each
                # row's own accepted prefix + fix/bonus token. The
                # exactness machinery is THE SHARED CORE
                # (_make_spec_round_core) speculative_generate uses — the
                # server only owns the schedule: per-row commits
                # (adjust_n identity), capacity parking, and the
                # host-side eos/max_new cutting in _append_tokens
                # (garbage rows are discarded by the occupancy snapshot).
                t_cache, d_cache, tok = carry
                k_draft, k_accept, k_fix = jax.random.split(key, 3)
                idx0 = _get_cache_index(t_cache)  # (slots,) round frontier

                t_cache, d_cache, w, _, n_eff = round_core(
                    t_cache, d_cache, tok, idx0, k_draft, k_accept, k_fix,
                    lambda n_raw: n_raw,          # pure per-row commits
                    lambda n_eff: idx0 + n_eff + 1)
                counts = n_eff + 1
                # Idle rows' frontiers park at the cap (same clamp
                # rationale as the plain path; spec_cap includes the
                # overshoot slack so live rows never clamp).
                new_idx = jnp.minimum(idx0 + counts, spec_cap)
                t_cache = _set_cache_index(t_cache, new_idx)
                d_cache = _set_cache_index(d_cache, new_idx)
                tok_next = w[jnp.arange(slots), n_eff]
                return (t_cache, d_cache, tok_next), (w, counts)

            @partial(jax.jit, donate_argnums=(2, 3, 4, 5))
            def spec_decode_step(params_c, draft_params_c, t_cache, d_cache,
                                 toks, key):
                key, sub = jax.random.split(key)
                round_core = _make_spec_round_core(
                    self._dm, self._dm_draft, params_c, draft_params_c,
                    gamma, greedy, probs_of, t_ring, d_ring)
                (t_cache, d_cache, toks), (w, counts) = jax.lax.scan(
                    partial(spec_round, round_core), (t_cache, d_cache, toks),
                    jax.random.split(sub, steps_per_call))
                # (slots, rounds, gamma+1) committed blocks + per-round
                # per-row commit counts.
                return (t_cache, d_cache, toks, w.swapaxes(0, 1),
                        counts.swapaxes(0, 1), key)

            @partial(jax.jit, donate_argnums=(2, 3, 4),
                     static_argnames=("chunk",))
            def spec_prefill_slots(params_c, draft_params_c, t_cache, d_cache,
                                   toks, prompts, rows, key, chunk):
                # Same row surgery as the plain path, on BOTH caches: the
                # draft must hold the prompt K/V before it can propose.
                key, sub = jax.random.split(key)
                row = jax.tree.map(lambda a: a[rows], t_cache)
                row = _set_cache_index(row, 0)
                row, last = _prefill(self._dm, params_c, row, prompts,
                                     chunk)
                t_cache = jax.tree.map(
                    lambda a, rw: a.at[rows].set(rw), t_cache, row)
                drow = jax.tree.map(lambda a: a[rows], d_cache)
                drow = _set_cache_index(drow, 0)
                drow, _ = _prefill(self._dm_draft, draft_params_c, drow,
                                   prompts, chunk)
                d_cache = jax.tree.map(
                    lambda a, rw: a.at[rows].set(rw), d_cache, drow)
                tok = sample(last, sub)  # (n,)
                toks = toks.at[rows].set(tok)
                return t_cache, d_cache, toks, tok, key

            self._spec_decode_step = partial(spec_decode_step, params,
                                             draft_params)
            self._spec_prefill_slots = partial(spec_prefill_slots, params,
                                               draft_params)
            self.stats["spec_rounds"] = 0
            self.stats["spec_committed"] = 0
        self._decode_step = partial(decode_step, params)
        self._prefill_slots = partial(prefill_slots, params)

    def submit(self, prompt, max_new_tokens: int) -> int:
        """Enqueue one request; returns its id. Slot assignment happens at
        the next step()/run() boundary — deferring it there lets a burst
        of submissions prefill as ONE batched (n, p) dispatch instead of n
        singletons (submit-time assignment made the documented startup
        batching unreachable)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"prompt must be 1-D non-empty, got "
                             f"shape {prompt.shape}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new_tokens}) "
                f"exceeds max_len {self.max_len}")
        # Upload at submit time (async): the refill dispatch later reads
        # a device array instead of paying a device_put on the refill
        # path — the host-side equivalent of pinning the request queue.
        req = {"id": next(self._ids), "prompt": prompt,
               "prompt_dev": jnp.asarray(prompt[None]),
               "max_new": max_new_tokens, "chunks": [], "n_out": 0}
        self._pending.append(req)
        return req["id"]

    def kv_leaf_shapes(self, plen: int) -> list[tuple]:
        """Expected shapes of the per-leaf KV blocks `submit_kv` installs
        for a prompt of length `plen`, in shipping order: one
        (plen, kv_heads, head_dim) entry per cached_key/cached_value leaf
        (tree-flatten order — the prefill tier extracts in the same
        order)."""
        return [(plen,) + tuple(leaf.shape[2:])
                for leaf in _kv_leaves(self._cache)]

    def submit_kv(self, prompt, max_new_tokens: int, kv_rows, last_logits) -> int:
        """Enqueue one request whose prompt K/V was computed ELSEWHERE (a
        prefill rank) and shipped here: the refill installs `kv_rows` into
        the claimed slot instead of re-running prefill — the decode half
        of the disaggregated serving tier (tpunet.serve). `kv_rows` is a
        list of numpy arrays matching kv_leaf_shapes(len(prompt));
        `last_logits` is the prefill's final-position logit row (vocab,),
        from which the first token is sampled exactly like the
        local-prefill path (greedy outputs are bitwise-equal to
        single-host serving when the KV wire is exact)."""
        if self._draft is not None:
            raise ValueError(
                "submit_kv requires a non-speculative server: the draft "
                "cache has no shipped prompt K/V to propose from")
        if getattr(self.model, "attn_window", None) is not None:
            raise ValueError(
                "submit_kv requires a full-capacity cache (attn_window "
                "models keep a rolling ring whose slot->position mapping "
                "is not the shipped prefix layout)")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(f"prompt must be 1-D non-empty, got "
                             f"shape {prompt.shape}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new_tokens}) "
                f"exceeds max_len {self.max_len}")
        shapes = self.kv_leaf_shapes(prompt.size)
        if len(kv_rows) != len(shapes):
            raise ValueError(f"expected {len(shapes)} KV blocks, "
                             f"got {len(kv_rows)}")
        kv_rows = [np.asarray(b, np.float32) for b in kv_rows]
        for i, (blk, want) in enumerate(zip(kv_rows, shapes)):
            if tuple(blk.shape) != want:
                raise ValueError(
                    f"KV block {i} has shape {tuple(blk.shape)}, "
                    f"expected {want}")
        last_logits = np.asarray(last_logits, np.float32)
        if last_logits.shape != (self.model.vocab,):
            raise ValueError(
                f"last_logits must be ({self.model.vocab},), got "
                f"{last_logits.shape}")
        req = {"id": next(self._ids), "prompt": prompt,
               "max_new": max_new_tokens, "chunks": [], "n_out": 0,
               "kv_rows": kv_rows, "kv_logits": last_logits}
        self._pending.append(req)
        return req["id"]

    def _fill_slots(self, defer: bool = False) -> None:
        if not (self._free and self._pending):
            return
        if (len(self._free) < self.refill_coalesce and self._live
                and len(self._pending) > len(self._free)):
            return  # hold out for a batched refill (see refill_coalesce)
        # Claim every (request, slot) pair now, then prefill all claims of
        # the SAME prompt length in ONE batched dispatch (n-row gather ->
        # reset -> (n, p) prefill -> n-row scatter). Startup fills all
        # slots in one call instead of `slots`; steady-state refills are
        # usually singletons. Retraces are bounded by distinct (n, p)
        # pairs — bucket prompt lengths as with any static-shape stack.
        claims = []
        while self._free and self._pending:
            claims.append((self._pending.pop(0), self._free.pop()))
        by_len: dict[int, list] = {}
        by_len_kv: dict[int, list] = {}
        for req, r in claims:
            target = by_len_kv if "kv_rows" in req else by_len
            target.setdefault(req["prompt"].size, []).append((req, r))

        def commit(group, tok):
            if defer:
                # Pipelined mode: don't sync on the refill's sampled
                # tokens (that would drain every in-flight window behind
                # them). Hold the device vector; the next absorb resolves
                # it BEFORE appending that window's tokens, so outputs and
                # retirement decisions are unchanged — only their
                # host-side timing shifts to the next window boundary.
                holder = {"dev": tok, "np": None}  # one readback, shared
                for i, (req, r) in enumerate(group):
                    self._live[r] = req
                    req["_pending"] = (holder, i)
            else:
                arr = np.asarray(tok)
                for i, (req, r) in enumerate(group):
                    self._live[r] = req
                    self._append_tokens(r, req, arr[i: i + 1])

        for group in by_len.values():
            reqs = [q for q, _ in group]
            rows = jnp.asarray(np.array([r for _, r in group], np.int32))
            prompts = (reqs[0]["prompt_dev"] if len(reqs) == 1
                       else jnp.concatenate(
                           [q["prompt_dev"] for q in reqs], axis=0))
            if self._draft is not None:
                (self._cache, self._dcache, self._toks, tok,
                 self._key) = self._spec_prefill_slots(
                    self._cache, self._dcache, self._toks, prompts, rows,
                    self._key, self._prefill_chunk)
            else:
                (self._cache, self._toks, tok,
                 self._key) = self._prefill_slots(
                    self._cache, self._toks, prompts, rows,
                    self._key, self._prefill_chunk)
            self.stats["prefills"] += len(group)
            commit(group, tok)
        for group in by_len_kv.values():
            # Shipped-KV refill (disaggregated serving): one batched adopt
            # dispatch per same-length group — the row surgery writes the
            # shipped prefix instead of recomputing it.
            reqs = [q for q, _ in group]
            rows = jnp.asarray(np.array([r for _, r in group], np.int32))
            kv = tuple(
                jnp.asarray(np.stack([q["kv_rows"][i] for q in reqs]))
                for i in range(len(reqs[0]["kv_rows"])))
            last = jnp.asarray(np.stack([q["kv_logits"] for q in reqs]))
            for q in reqs:  # the device copies above own the data now
                q.pop("kv_rows")
                q.pop("kv_logits")
            (self._cache, self._toks, tok,
             self._key) = self._adopt_slots(
                self._cache, self._toks, kv, last, rows, self._key)
            self.stats["kv_adopts"] += len(group)
            commit(group, tok)

    def _append_tokens(self, r: int, req: dict, toks_np) -> None:
        """Commit a window's tokens to a request — vectorized: cut at
        max_new, then at the first eos, in one numpy pass instead of a
        Python loop per token. Retires the request (freeing its slot into
        the done buffer) when either bound is hit; a request can finish at
        ANY commit point, including its first prefill-sampled token."""
        take = min(req["max_new"] - req["n_out"], len(toks_np))
        first = req["n_out"] == 0
        chunk = toks_np[:take]
        if self.eos_id is not None:
            hits = np.nonzero(chunk == self.eos_id)[0]
            if hits.size:
                chunk = chunk[: hits[0] + 1]  # keep the eos itself
        req["chunks"].append(chunk)
        req["n_out"] += len(chunk)
        if first and len(chunk) and self._on_first_token is not None:
            self._on_first_token(req["id"])  # TTFT hook (serving tier)
        if (req["n_out"] >= req["max_new"]
                or (self.eos_id is not None and chunk.size
                    and chunk[-1] == self.eos_id)):
            del self._live[r]
            self._free.append(r)
            self._done_buffer.append(
                {"id": req["id"], "prompt": req["prompt"],
                 "tokens": np.concatenate(req["chunks"]).astype(np.int32)})

    def _dispatch_window(self):
        """Issue one decode window WITHOUT reading it back; returns the
        device payload plus a {slot: request_id} snapshot of occupancy at
        dispatch time (a later refill recycles the slot for a different
        request — that window's tokens for the slot are garbage). Payload:
        plain mode (window, None); speculative mode (w, counts) with w
        (slots, rounds, gamma+1) and per-round per-row commit counts."""
        if self._draft is not None:
            (self._cache, self._dcache, self._toks, w, counts,
             self._key) = self._spec_decode_step(
                self._cache, self._dcache, self._toks, self._key)
            self.stats["decode_windows"] += 1
            return (w, counts), {r: req["id"]
                                 for r, req in self._live.items()}
        self._cache, self._toks, window, self._key = self._decode_step(
            self._cache, self._toks, self._key)
        self.stats["decode_windows"] += 1
        return (window, None), {r: req["id"]
                                for r, req in self._live.items()}

    def _absorb_window(self, payload, ids_at_dispatch) -> None:
        window, counts = payload
        window = np.asarray(window)  # readback
        counts = None if counts is None else np.asarray(counts)
        for r, rid in ids_at_dispatch.items():
            req = self._live.get(r)
            if req is None or req["id"] != rid:
                continue  # retired or recycled since this window launched
            if "_pending" in req:
                # Deferred prefill token: by now its compute long finished
                # (it was dispatched before this window). The group's
                # token vector is read back once and shared.
                holder, i = req.pop("_pending")
                if holder["np"] is None:
                    holder["np"] = np.asarray(holder["dev"])
                self._append_tokens(r, req, holder["np"][i: i + 1])
                if r not in self._live:
                    continue
            if counts is None:
                self._append_tokens(r, req, window[r])
                continue
            for j in range(window.shape[1]):  # speculative rounds
                c = int(counts[r, j])
                self.stats["spec_rounds"] += 1
                self.stats["spec_committed"] += c
                self._append_tokens(r, req, window[r, j, :c])
                if r not in self._live:
                    break  # rest of this row's rounds are garbage

    def step(self) -> list[dict]:
        """Advance every live slot one token; returns the requests that
        finished this step as {"id", "prompt", "tokens"} dicts (freed
        slots are immediately refilled from the queue)."""
        self._fill_slots()
        if self._live:
            window, ids = self._dispatch_window()
            self._absorb_window(window, ids)
            self._fill_slots()
        finished, self._done_buffer = self._done_buffer, []
        return finished

    def run(self, *, pipeline: int = 1) -> dict[int, np.ndarray]:
        """Drive the server until every submitted request finishes;
        returns {request_id: generated tokens}.

        `pipeline` keeps that many decode windows in flight: window k+1 is
        dispatched BEFORE window k's readback, so host bookkeeping (token
        appends, retirement, refill decisions) overlaps device compute
        instead of serializing with it. A window launched before a refill
        simply decodes garbage in the recycled slot (discarded via the
        dispatch-time occupancy snapshot) and the refilled request joins
        one window later — greedy outputs are unchanged (each request's
        tokens depend only on its own prefix); with temperature > 0 the
        carried key chain advances differently across pipeline settings,
        so sampled outputs are schedule-dependent (still exactly
        distributed). pipeline=1 (the default) is the strict
        alternate-dispatch-absorb loop — right for single-core hosts and
        CPU testing, where host and compute serialize anyway and extra
        in-flight windows just waste micro-steps. pipeline=2 is the TPU
        serving setting: compute runs on the chip, so the host's
        absorb/refill work for window k hides entirely under window k+1's
        device time."""
        if pipeline < 1:
            raise ValueError(f"pipeline must be >= 1, got {pipeline}")
        results = {}
        inflight = deque()
        # defer only when windows are actually kept in flight: at
        # pipeline=1 nothing is behind the prefill to stall, and the
        # immediate readback lets a request that finishes on its
        # prefill-sampled token (max_new=1, eos first) retire with ZERO
        # decode windows; deferred it would cost a whole discarded window.
        defer = pipeline >= 2
        while (self._live or self._pending or self._done_buffer
               or inflight):
            finished, self._done_buffer = self._done_buffer, []
            for rec in finished:
                results[rec["id"]] = rec["tokens"]
            self._fill_slots(defer=defer)  # no-op without free+pending
            while self._live and len(inflight) < pipeline:
                inflight.append(self._dispatch_window())
            if inflight:
                window, ids = inflight.popleft()
                self._absorb_window(window, ids)
                self._fill_slots(defer=defer)
        return results
