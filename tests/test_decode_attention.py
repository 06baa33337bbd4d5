"""The decode step's attention (``layers.decode_attention``), which attends
to the cached keys before ``pos`` and to the step's own K/V apart and merges
the two, against a plain float32 softmax over the cache with the step's
K/V written at ``pos``, and against ``layers.attention`` over that written
cache (its long-cache branch from 4096 positions on, the blockwise one
below).  Whatever the cache holds at and after ``pos`` is noise, and must
not leak in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers


def _reference(q, k_cache, v_cache, k, v, pos, window):
    """softmax(q K^T / sqrt(Dh)) V in float64 over the cache with k/v
    written at pos, each query masked to the causal window."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    kc = np.array(k_cache, np.float64)
    vc = np.array(v_cache, np.float64)
    B, T, H, Dh = q.shape
    Kh = kc.shape[2]
    kc[:, pos:pos + T], vc[:, pos:pos + T] = k, v
    kc = np.repeat(kc, H // Kh, axis=2)
    vc = np.repeat(vc, H // Kh, axis=2)
    s = np.einsum("bthd,bshd->bhts", q, kc) / np.sqrt(Dh)
    pos_k = np.arange(kc.shape[1])
    q_pos = pos + np.arange(T)
    mask = pos_k[None, :] <= q_pos[:, None]
    if window:
        mask &= pos_k[None, :] > q_pos[:, None] - window
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhts,bshd->bthd", p, vc)


# (S, pos, window, H, Kh, T, dtype)
CASES = {
    "short-G1": (64, 37, 0, 4, 4, 1, jnp.float32),
    "short-G3": (64, 37, 0, 6, 2, 1, jnp.float32),
    "short-window": (64, 37, 16, 6, 2, 1, jnp.float32),
    "short-pos0": (64, 0, 0, 6, 2, 1, jnp.float32),
    "short-last": (64, 63, 0, 6, 2, 1, jnp.float32),
    "blockwise-G3": (2500, 2100, 0, 6, 2, 1, jnp.float32),
    "long-G3": (4096, 3000, 0, 6, 2, 1, jnp.float32),
    "long-window-G1": (4096, 3000, 1024, 4, 4, 1, jnp.float32),
    "long-pos0": (4096, 0, 0, 6, 2, 1, jnp.float32),
    "long-bf16": (4096, 1500, 0, 6, 2, 1, jnp.bfloat16),
    "short-three-queries": (64, 20, 8, 6, 2, 3, jnp.float32),
}


@pytest.mark.parametrize("case", CASES)
def test_decode_attention_matches_softmax_over_written_cache(case):
    S, pos, window, H, Kh, T, dt = CASES[case]
    B, Dh = 2, 16
    rng = np.random.default_rng(sum(map(ord, case)))

    def draw(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dt)

    q = draw(B, T, H, Dh)
    k, v = draw(B, T, Kh, Dh), draw(B, T, Kh, Dh)
    # what lies at and past pos is noise, large enough to show if read
    k_cache, v_cache = draw(B, S, Kh, Dh), draw(B, S, Kh, Dh)
    k_cache = k_cache.at[:, pos:].multiply(30)
    v_cache = v_cache.at[:, pos:].set(1e4)

    # the decode cache is stored head-major
    attend = jax.jit(layers.decode_attention, static_argnames="window")
    got = attend(q, k_cache.swapaxes(1, 2), v_cache.swapaxes(1, 2), k, v,
                 pos=jnp.asarray(pos, jnp.int32), window=window)
    assert got.shape == (B, T, H, Dh) and got.dtype == dt
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    want = _reference(q, k_cache, v_cache, k, v, pos, window)
    tol = 1e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    written = (k_cache.at[:, pos:pos + T].set(k),
               v_cache.at[:, pos:pos + T].set(v))
    today = layers.attention(q, *written, causal=True, q_offset=pos,
                             window=window, kv_len=pos + T)
    np.testing.assert_allclose(got, np.asarray(today, np.float32),
                               rtol=tol, atol=tol)
    if pos == 0 and T == 1:
        # nothing cached to attend to: each query head reads its KV head's v
        np.testing.assert_allclose(
            got, np.repeat(np.asarray(v, np.float32), H // Kh, axis=2),
            rtol=tol, atol=tol)
