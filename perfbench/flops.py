"""Operations and bytes of attention, from a configuration's shapes alone;
what belongs to one model family stands in perfbench/models/<family>.py.
Kept with the benchmark so that no later PR can move the yardstick.
Recompute (remat) is never counted; causal attention counts the half that
is needed, and a sliding window the keys inside it."""

from __future__ import annotations


def mean_keys(seq: int, window: int | None) -> float:
    """Keys a query attends to, averaged over the positions of a sequence of
    `seq` tokens under the causal mask and the sliding window."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    inside = window * (window + 1) / 2          # positions 0 .. window-1
    return (inside + (seq - window) * window) / seq


def attention_flops_fwd(c: dict, batch: int, seq: int) -> float:
    """QK^T and PV of one layer, forward, useful part only."""
    d = c["num_attention_heads"] * c["head_dim"]
    return 4.0 * d * mean_keys(seq, c.get("sliding_window")) * batch * seq


def flash_bytes_fwd(c: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of one layer's attention forward: read q, k, v and
    write o once (k and v at their own, grouped, width)."""
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return float(batch * seq * dh * (2 * h + 2 * kv) * itemsize)


def flash_bytes_bwd(c: dict, batch: int, seq: int, itemsize: int = 2) -> float:
    """Backward: read q, k, v, o, do; write dq, dk, dv."""
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return float(batch * seq * dh * (4 * h + 4 * kv) * itemsize)
