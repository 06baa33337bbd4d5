"""Pallas TPU kernel for the RG-LRU gated linear recurrence (Griffin).

    h_t = a_t * h_{t-1} + b_t        (elementwise in the feature dim)

Feature dim tiled over a parallel grid axis (lane-aligned blocks of 128);
time tiled over a sequential grid axis with the running h carried in VMEM
scratch; within a time block a ``fori_loop`` steps the recurrence (the op
is bandwidth-bound, so the VPU loop is fine — the win is keeping h
resident in VMEM instead of round-tripping HBM each step).

Layout: a, b: (B, T, R) -> h: (B, T, R).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ROWS = 16


def _kernel(a_ref, b_ref, h0_ref, y_ref, h_ref, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    def step(g, h):                      # h: (1, block_r)
        # load and store whole (_ROWS, block_r) tiles: Mosaic needs dynamic
        # sublane offsets aligned to the tile (16 rows covers bf16 too)
        t0 = pl.multiple_of(g * _ROWS, _ROWS)
        a = a_ref[0, pl.ds(t0, _ROWS), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(t0, _ROWS), :].astype(jnp.float32)
        hs = []
        for i in range(_ROWS):
            h = a[i:i + 1] * h + b[i:i + 1]
            hs.append(h)
        y_ref[0, pl.ds(t0, _ROWS), :] = \
            jnp.concatenate(hs, axis=0).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t // _ROWS, step, h_ref[...])
    h_ref[...] = h


def rg_lru_scan(a, b, h0, *, block_t: int = 128, block_r: int = 512,
                interpret: bool = False):
    """a, b: (B, T, R); h0: (B, R) -> h: (B, T, R) (all steps' states)."""
    B, T, R = a.shape
    block_t = min(block_t, T)
    block_r = min(block_r, R)
    assert T % block_t == 0 and R % block_r == 0, (T, R, block_t, block_r)
    assert block_t % _ROWS == 0, (block_t, _ROWS)
    grid = (B, R // block_r, T // block_t)
    spec = pl.BlockSpec((1, block_t, block_r),
                        lambda bb, ri, ti: (bb, ti, ri))
    # h0 as (B, 1, R): a (1, block_r) block then spans the array's
    # second-minor dim, as the TPU's (8, 128) tiling rule requires
    h0_spec = pl.BlockSpec((1, 1, block_r), lambda bb, ri, ti: (bb, 0, ri))
    kern = functools.partial(_kernel, block_t=block_t)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[spec, spec, h0_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, T, R), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, R))
