"""Pallas TPU flash attention (causal / windowed, GQA).

Grid: (B, H, q_blocks, kv_blocks) — kv innermost, sequential ("arbitrary"),
carrying the online-softmax state (m, l, acc) in VMEM scratch.  Q/K/V are
tiled into (block_q x head_dim) / (block_k x head_dim) VMEM blocks; the
MXU sees (block_q x head_dim) @ (head_dim x block_k) and
(block_q x block_k) @ (block_k x head_dim) matmuls, with block sizes
multiples of the 128-lane tile.  GQA is expressed in the K/V index_map
(kv head = h // group), so K/V are never repeated in HBM.

Layout contract (ops.py transposes from the model's (B, T, H, D)):
  q: (B, H, T, D);  k, v: (B, Kh, S, D);  out: (B, H, T, D).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, nk: int, causal: bool,
            window: int, scale: float, kv_len: Optional[int]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    run = True
    if causal:
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run if isinstance(run, bool) else run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        if kv_len is not None:
            mask &= k_pos < kv_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    kv_len: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jnp.ndarray:
    """q: (B, H, T, D); k, v: (B, Kh, S, D) -> (B, H, T, D)."""
    B, H, T, D = q.shape
    Kh, S = k.shape[1], k.shape[2]
    G = H // Kh
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    assert T % block_q == 0 and S % block_k == 0, (T, S, block_q, block_k)
    nq, nk = T // block_q, S // block_k
    scale = 1.0 / math.sqrt(D)

    grid = (B, H, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, qi, ki: (b, h // G, ki, 0))
    v_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, qi, ki: (b, h // G, ki, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, D), jnp.float32),
    ]

    kern = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                             nk=nk, causal=causal, window=window,
                             scale=scale, kv_len=kv_len)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
