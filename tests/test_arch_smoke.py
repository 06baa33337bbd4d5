"""Per-architecture smoke tests (deliverable f).

Every assigned architecture instantiates a REDUCED config of the same
family and runs: one forward/train step (shapes + finite loss), one
prefill + decode step, and — for autoregressive-consistency — checks that
prefill-then-decode matches a longer forward's last-token logits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get, reduced, registry
from repro.models import api
from repro.optim.adamw import AdamWConfig
from repro.train.step import init_train_state, make_train_step

ARCHS = sorted(registry().keys())
SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")


def _smoke_batch(cfg):
    return {k: jnp.asarray(v)
            for k, v in api.make_batch(cfg, SMOKE_SHAPE, seed=1).items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_runs_and_is_finite(arch):
    cfg = reduced(get(arch))
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    batch = _smoke_batch(cfg)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    state, metrics = step(state, batch)
    loss0 = float(metrics["loss"])
    assert np.isfinite(loss0), f"{arch}: non-finite loss"
    # loss should be near ln(vocab) at init
    assert 0.5 * np.log(cfg.vocab) < loss0 < 3.0 * np.log(cfg.vocab)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_training_reduces_loss(arch):
    cfg = reduced(get(arch))
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    batch = _smoke_batch(cfg)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=2e-3)))
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.1, f"{arch}: no learning {losses}"


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_and_decode_shapes(arch):
    cfg = reduced(get(arch))
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    batch = _smoke_batch(cfg)
    cache, logits = api.prefill(params, cfg, batch)
    assert logits.shape[0] == SMOKE_SHAPE.global_batch
    assert logits.shape[-1] == cfg.vocab
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()
    # one decode step
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    pos = jnp.asarray(batch["tokens"].shape[1], jnp.int32)
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        # decode needs a max-length cache: re-run prefill into a padded one
        cache2 = _padded_cache(cfg, params, batch)
        logits2, cache3 = api.decode_step(params, cfg, tok, pos, cache2)
    else:
        logits2, cache3 = api.decode_step(params, cfg, tok, pos, cache)
    assert logits2.shape == (SMOKE_SHAPE.global_batch, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2, dtype=np.float32)).all()


def _padded_cache(cfg, params, batch, max_len=64):
    """Prefill then copy the collected KV into a max_len cache."""
    cache, _ = api.prefill(params, cfg, batch)
    if cfg.family == "encdec":
        full = api.init_cache(cfg, batch["tokens"].shape[0], max_len)
        S = cache["k"].shape[2]
        for key in ("k", "v"):
            full[key] = full[key].at[:, :, :S].set(cache[key])
        full["mk"], full["mv"] = cache["mk"], cache["mv"]
        return full
    full = api.init_cache(cfg, batch["tokens"].shape[0], max_len)
    return {name: jax.lax.dynamic_update_slice_in_dim(
                full[name], cache[name], 0, axis=api.kv_seq_axis(cfg))
            for name in ("k", "v")}


@pytest.mark.parametrize("arch", ["llama3-8b", "rwkv6-7b",
                                  "recurrentgemma-9b", "gemma-2b"])
def test_decode_consistency_with_forward(arch):
    """prefill(t[:n]) + decode(t[n]) logits == forward(t[:n+1]) last logits."""
    cfg = reduced(get(arch))
    params = api.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(7)
    n = 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(2, n + 1)),
                       dtype=jnp.int32)
    # reference: full forward on n+1 tokens
    batch_full = {"tokens": toks}
    cache_full, logits_full = api.prefill(params, cfg, batch_full)
    # prefill n, decode token n
    batch_n = {"tokens": toks[:, :n]}
    if cfg.family in ("dense", "moe"):
        cache = _padded_cache(cfg, params, batch_n, max_len=n + 8)
    else:
        cache, _ = api.prefill(params, cfg, batch_n)
    logits_dec, _ = api.decode_step(params, cfg, toks[:, n:n + 1],
                                    jnp.asarray(n, jnp.int32), cache)
    got = np.asarray(logits_dec[:, 0], dtype=np.float32)
    want = np.asarray(logits_full[:, -1], dtype=np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_param_counts_match_scale():
    """Full configs should land near their nominal parameter counts."""
    expect = {"llama3-8b": (7e9, 9.5e9),
              "phi3-medium-14b": (12e9, 16e9),
              "starcoder2-7b": (6e9, 9e9),
              "gemma-2b": (2e9, 3.3e9),
              "grok-1-314b": (2.7e11, 3.4e11),
              # the brief's 48L x 64e x 1408 config computes to ~28B total
              # (nominal "16B" assumes fewer MoE layers); brief config wins
              "moonshot-v1-16b-a3b": (2.4e10, 3.1e10),
              "rwkv6-7b": (6e9, 9e9),
              "recurrentgemma-9b": (7.5e9, 11e9)}
    for arch, (lo, hi) in expect.items():
        n = get(arch).param_count()
        assert lo < n < hi, f"{arch}: {n:.3g} params outside [{lo}, {hi}]"
