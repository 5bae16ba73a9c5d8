"""Prefill tier: prompt ingestion -> shippable KV blocks.

The prefill rank runs EXACTLY the computation the single-host BatchServer's
refill path runs — the same ``_prefill`` on the same (1, p) shapes with the
same decode/per-row clone — so the extracted K/V prefix and final-position
logits are bitwise what a local prefill would have produced. That identity
is the whole disaggregation contract: ship those bytes over an exact (f32)
wire, adopt them into a decode slot, and the greedy token stream cannot be
told apart from single-host serving.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpunet.models.generate import (_kv_leaves, _prefill, _set_cache_index,
                                    init_cache)


class PrefillEngine:
    """One-slot prompt-ingestion engine for the frontend tier.

    Holds a single persistent decode-cache row (donated through every
    call, like the BatchServer's); each ``prefill()`` resets the row's
    index and fills positions ``[0, p)``, then extracts the per-layer
    K/V prefixes in ``_kv_leaves`` order plus the last-position logits.
    One retrace per distinct prompt length — bucket or pad prompt lengths
    exactly as with any static-shape serving stack.
    """

    def __init__(self, model, params, *, max_len: int,
                 prefill_chunk: int | None = None):
        if getattr(model, "n_experts", 0):
            raise ValueError(
                "PrefillEngine requires a dense model (same MoE "
                "batch-coupling argument as the BatchServer)")
        if getattr(model, "attn_window", None) is not None:
            raise ValueError(
                "PrefillEngine requires a full-capacity cache: windowed "
                "ring caches do not keep the shipped-prefix layout")
        self.model = model
        self.max_len = max_len
        self._dm = model.clone(decode=True, per_row_cache=True)
        self._cache = init_cache(self._dm, 1, max_len)
        self._chunk = prefill_chunk
        self.stats = {"prefills": 0}

        # params are an argument, not a closure: see BatchServer.
        @partial(jax.jit, donate_argnums=(1,), static_argnames=("chunk",))
        def prefill_one(params_c, cache, prompt, chunk):
            cache = _set_cache_index(cache, 0)
            return _prefill(self._dm, params_c, cache, prompt, chunk)

        self._prefill_one = partial(prefill_one, params)

    def kv_leaf_shapes(self, plen: int) -> list[tuple]:
        """Per-leaf KV block shapes for a prompt of length `plen` — must
        equal the decode tier's ``BatchServer.kv_leaf_shapes(plen)``."""
        return [(plen,) + tuple(leaf.shape[2:])
                for leaf in _kv_leaves(self._cache)]

    def prefill(self, prompt) -> tuple[list[np.ndarray], np.ndarray]:
        """Run prompt ingestion; returns (kv_rows, last_logits) — the
        per-leaf f32 K/V prefixes and the final-position logit row, ready
        for ``serve.kv.encode_kv_block`` / ``BatchServer.submit_kv``."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(
                f"prompt must be 1-D non-empty, got shape {prompt.shape}")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) must leave room for generation "
                f"under max_len {self.max_len}")
        self._cache, last = self._prefill_one(
            self._cache, jnp.asarray(prompt[None]), self._chunk)
        plen = prompt.size
        kv_rows = [np.asarray(leaf[0, :plen], np.float32)
                   for leaf in _kv_leaves(self._cache)]
        self.stats["prefills"] += 1
        return kv_rows, np.asarray(last[0], np.float32)
