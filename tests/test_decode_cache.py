"""What one decode step does to the KV cache, for each family that keeps
one stacked (L, B, S, Kh, Dh) cache: it writes the step's K/V at ``pos``
in every layer, the K/V a prefill of the sequence one token longer gives
there, and leaves every other position as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, reduced
from repro.models import api

N = 12


def _f32(a):
    return np.asarray(a, dtype=np.float32)


@pytest.mark.parametrize("arch", ["llama3-8b", "moonshot-v1-16b-a3b",
                                  "internvl2-1b"])
def test_decode_step_writes_only_pos(arch):
    cfg = reduced(get(arch))
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(11)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(2, N + 1)),
                       dtype=jnp.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = jnp.asarray(
            rng.normal(size=(2, cfg.frontend_len, cfg.d_model)) * 0.1,
            dtype=jnp.float32)
    short, _ = api.prefill(params, cfg, {"tokens": toks[:, :N], **extra})
    longer, _ = api.prefill(params, cfg, {"tokens": toks, **extra})
    pos = short["k"].shape[2]       # prompt length, patches included

    # the prefill's K/V, then noise in every position past it
    cache = {}
    for name in ("k", "v"):
        noise = jnp.asarray(
            rng.normal(size=short[name].shape[:2] + (pos + 8,)
                       + short[name].shape[3:]), short[name].dtype)
        cache[name] = noise.at[:, :, :pos].set(short[name])
    before = {name: _f32(c) for name, c in cache.items()}

    step = jax.jit(api.decode_step, static_argnums=1)
    _, after = step(params, cfg, toks[:, N:], jnp.asarray(pos, jnp.int32),
                    cache)
    for name in ("k", "v"):
        got = _f32(after[name])
        assert got.shape == before[name].shape
        np.testing.assert_array_equal(got[:, :, :pos],
                                      before[name][:, :, :pos])
        np.testing.assert_array_equal(got[:, :, pos + 1:],
                                      before[name][:, :, pos + 1:])
        np.testing.assert_allclose(got[:, :, pos], _f32(longer[name][:, :, pos]),
                                   rtol=2e-2, atol=2e-2, err_msg=name)
