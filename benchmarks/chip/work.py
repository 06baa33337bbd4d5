"""Operations and bytes that the model's work needs, from its shapes.

Model FLOPs count each multiply-add of a matrix product as two operations.
Causal attention counts each (query, key) pair it needs once, within the
sliding window where the model has one: the pairs a blockwise kernel
computes and then masks are not work.  Recomputed
operations (remat) are not counted.  Bytes are what a step must move
through HBM at least: each weight once, and of the KV cache only the live
positions.  ``s`` is ``cell.sizes(...)``.
"""

from __future__ import annotations

import numpy as np

BF16 = 2


def layer_weights(s) -> int:
    """Matrix parameters of one layer (the norms are negligible)."""
    d, hd = s["d_model"], s["head_dim"]
    attn = 2 * d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd
    mlp = (2 if s["act"] == "gelu" else 3) * d * s["d_ff"]
    return attn + mlp


def head_weights(s) -> int:
    return s["d_model"] * s["vocab"]


def pairs(queries: int, keys_before: int, window: int) -> int:
    """(query, key) pairs of ``queries`` new positions that see
    ``keys_before`` earlier keys and each other causally, each query at
    most ``window`` keys (0: no window)."""
    seen = keys_before + 1 + np.arange(queries)
    return int(np.minimum(seen, window).sum() if window else seen.sum())


def attention_flops(s, batch: int, queries: int, keys_before: int = 0
                    ) -> float:
    """QK^T and PV over every layer for the ``pairs`` of ``queries`` new
    positions."""
    return 4.0 * s["layers"] * batch * s["heads"] * s["head_dim"] * \
        pairs(queries, keys_before, s["window"])


def attention_bytes(s, batch: int, queries: int, keys: int) -> float:
    """Q and O of the new positions, K and V of every key, per layer."""
    q = batch * queries * s["heads"] * s["head_dim"] * BF16
    kv = batch * keys * s["kv_heads"] * s["head_dim"] * BF16
    return float(s["layers"] * (2 * q + 2 * kv))


def prefill(s, batch: int, prompt: int) -> dict:
    """One prefill call: every layer over every prompt position, the LM
    head over the last position only (what the program returns)."""
    dense = 2.0 * batch * prompt * s["layers"] * layer_weights(s) + \
        2.0 * batch * head_weights(s)
    att = attention_flops(s, batch, prompt)
    weights = (s["layers"] * layer_weights(s) + head_weights(s)) * BF16
    kv_out = 2 * s["layers"] * batch * prompt * s["kv_heads"] * \
        s["head_dim"] * BF16
    return {"flops": dense + att, "bytes": float(weights + kv_out),
            "attention_flops": att,
            "attention_bytes": attention_bytes(s, batch, prompt, prompt)}


def decode_step(s, batch: int, pos: int) -> dict:
    """One decode step at position ``pos``: one token per request that
    attends to its live keys, ``pos + 1`` or the window."""
    live = pairs(1, pos, s["window"])
    dense = 2.0 * batch * (s["layers"] * layer_weights(s) + head_weights(s))
    att = attention_flops(s, batch, 1, pos)
    weights = (s["layers"] * layer_weights(s) + head_weights(s)) * BF16
    embed_rows = batch * s["d_model"] * BF16
    kv_read = 2 * s["layers"] * batch * live * s["kv_heads"] * \
        s["head_dim"] * BF16
    kv_write = 2 * s["layers"] * batch * s["kv_heads"] * s["head_dim"] * BF16
    return {"flops": dense + att,
            "bytes": float(weights + embed_rows + kv_read + kv_write),
            "attention_flops": att,
            "attention_bytes": attention_bytes(s, batch, 1, live)}


def add(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in set(a) | set(b)}
