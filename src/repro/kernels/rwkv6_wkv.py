"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked parallel form).

Per (batch, head) the recurrence over T steps with state S in R^{NxN}:
    y_t = (S_{t-1} + (u * k_t) v_t^T)^T r_t
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
is evaluated chunk by chunk (grid innermost dim sequential, state carried
in VMEM scratch).  Within a chunk all decay factors appear as
exp(c_i - c_j) with i >= j <= 0 — numerically safe (DESIGN.md §7).

Layout: r, k, v, logw: (B, H, T, N); u: (H, N); y: (B, H, T, N).
Chunk length C is the sublane-friendly 32; N = head dim (64 for rwkv6-7b).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_ref, *,
            chunk: int, n: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)          # log decay, < 0
    u = u_ref[0].astype(jnp.float32)             # (1, N) -> broadcast
    S = state_ref[...]                           # (N, N)

    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum); HIGHEST keeps the f32 log-decays exact enough to exp
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >=
           jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    c = jax.lax.dot_general(tri.astype(jnp.float32), w,
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    c_prev = c - w                               # exclusive
    c_end = c[-1:]                               # (1, N)

    # intra-chunk scores[t,s] = sum_n r[t,n] k[s,n] exp(c_prev[t]-c[s]) s<t
    expo = c_prev[:, None, :] - c[None, :, :]    # (C, C, N)
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, n), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, n), 1)
    expo = jnp.where(mask, expo, -jnp.inf)
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(expo), axis=-1)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # bonus diagonal term: (r . (u*k)) v
    y += jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    # carried state contribution
    y += jax.lax.dot_general(r * jnp.exp(c_prev), S,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update
    khat = k * jnp.exp(c_end - c)                # (C, N)
    state_ref[...] = S * jnp.exp(c_end[0])[:, None] + jax.lax.dot_general(
        khat, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def wkv6(r, k, v, logw, u, *, chunk: int = 32, interpret: bool = False):
    """r,k,v,logw: (B, H, T, N); u: (H, N) -> y: (B, H, T, N)."""
    B, H, T, N = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    grid = (B, H, nc)
    spec = pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, c, 0))
    # u as (H, 1, N): a (1, N) block then spans the array's last two dims,
    # as the TPU's (8, 128) tiling rule requires
    u_spec = pl.BlockSpec((1, 1, N), lambda b, h, c: (h, 0, 0))
    kern = functools.partial(_kernel, chunk=chunk, n=N)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[spec, spec, spec, spec, u_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(H, 1, N))
