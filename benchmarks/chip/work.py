"""Operations and bytes that the model's work needs, from its shapes.

Each family counts its own (``families/<family>.py``: ``prefill`` and
``decode_step``); ``prefill`` and ``decode_step`` here hand the count to
the family of ``s`` (``cell.sizes(...)``), and the rest is what every
family counts by.

Model FLOPs count each multiply-add of a matrix product as two operations.
Causal attention counts each (query, key) pair it needs once, within the
sliding window where the model has one: the pairs a blockwise kernel
computes and then masks are not work.  Recomputed
operations (remat) are not counted.  Bytes are what a step must move
through HBM at least: each weight once, and of the KV cache only the live
positions.
"""

from __future__ import annotations

import numpy as np

from cell import family

BF16 = 2


def pairs(queries: int, keys_before: int, window: int) -> int:
    """(query, key) pairs of ``queries`` new positions that see
    ``keys_before`` earlier keys and each other causally, each query at
    most ``window`` keys (0: no window)."""
    seen = keys_before + 1 + np.arange(queries)
    return int(np.minimum(seen, window).sum() if window else seen.sum())


def prefill(s, batch: int, prompt: int) -> dict:
    """One prefill call of ``batch`` prompts of ``prompt`` tokens."""
    return family(s["family"]).prefill(s, batch, prompt)


def decode_step(s, batch: int, pos: int) -> dict:
    """One decode step of ``batch`` requests at position ``pos``."""
    return family(s["family"]).decode_step(s, batch, pos)


def add(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0.0) + b.get(k, 0.0) for k in set(a) | set(b)}
