"""Unified model API: dispatch by architecture family.

Functions every family provides (shapes in family modules):
  init_params(key, cfg)                          -> params
  forward_hidden(params, cfg, batch)             -> (hidden, aux)   [train]
  prefill(params, cfg, batch)                    -> (cache, logits)
  decode_step(params, cfg, token, pos, cache)    -> (logits, cache)
  init_cache(cfg, batch, max_len)                -> cache           [decode]
  kv_seq_axis(cfg)       -> the sequence axis of a key/value cache's k, v
plus ``input_specs`` / ``make_batch`` describing the inputs of each shape
kind (tokens, labels, stub frame/patch embeddings).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, ShapeConfig
from . import encdec, griffin, rwkv6, transformer

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs for the dry-run; numpy for smoke tests)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Stand-ins for every model input of this (arch, shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    if shape.kind in ("train", "prefill"):
        if cfg.family == "encdec":
            # source frames (stub) scale with the shape's sequence length;
            # decoder text is S//8 tokens (ASR-ish compression)
            return {"frames": sds((B, S, cfg.d_model), f32),
                    "tokens": sds((B, max(S // 8, 16)), jnp.int32),
                    "labels": sds((B, max(S // 8, 16)), jnp.int32)}
        if cfg.family == "vlm":
            P = cfg.frontend_len
            return {"patches": sds((B, P, cfg.d_model), f32),
                    "tokens": sds((B, S - P), jnp.int32),
                    "labels": sds((B, S - P), jnp.int32)}
        return {"tokens": sds((B, S), jnp.int32),
                "labels": sds((B, S), jnp.int32)}
    # decode: one new token against a cache of S
    return {"token": sds((B, 1), jnp.int32)}


def make_batch(cfg: ArchConfig, shape: ShapeConfig, seed: int = 0
               ) -> Dict[str, np.ndarray]:
    """Synthetic concrete batch matching input_specs.

    Token streams are LEARNABLE: each sequence is an affine cycle
    ``tok[t+1] = (tok[t] + stride) % vocab`` with a per-sequence random
    start/stride, and ``labels`` are the next-token shift — so a real
    training run shows decreasing loss instead of noise around
    ln(vocab)."""
    rng = np.random.default_rng(seed)
    out = {}
    specs = input_specs(cfg, shape)
    for name, spec in specs.items():
        if spec.dtype != jnp.int32:
            out[name] = rng.normal(size=spec.shape).astype(np.float32) * 0.1
        elif name == "tokens" or name == "token":
            B = spec.shape[0]
            S = spec.shape[1] if len(spec.shape) > 1 else 1
            start = rng.integers(0, cfg.vocab, size=(B, 1))
            stride = rng.integers(1, min(cfg.vocab, 17), size=(B, 1))
            toks = (start + stride * np.arange(S)[None, :]) % cfg.vocab
            out[name] = toks.astype(np.int32)
    if "labels" in specs:
        toks = out["tokens"]
        out["labels"] = np.roll(toks, -1, axis=1).astype(np.int32)
        out["labels"][:, -1] = -1      # no target for the final position
    return out


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig) -> Params:
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.init_params(key, cfg)
    if cfg.family == "ssm":
        return rwkv6.init_params(key, cfg)
    if cfg.family == "hybrid":
        return griffin.init_params(key, cfg)
    if cfg.family == "encdec":
        return encdec.init_params(key, cfg)
    raise ValueError(cfg.family)


def forward_hidden(params: Params, cfg: ArchConfig, batch: Dict, *,
                   remat: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Hidden states for the training loss (+ MoE aux)."""
    zero = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "moe"):
        h, _, aux = transformer.forward(params, cfg, batch["tokens"],
                                        remat=remat)
        return h, aux
    if cfg.family == "vlm":
        h, _, aux = transformer.forward(params, cfg, batch["tokens"],
                                        patches=batch["patches"],
                                        remat=remat)
        # loss only over the text positions
        P = cfg.frontend_len
        return h[:, P:], aux
    if cfg.family == "ssm":
        h, _ = rwkv6.forward(params, cfg, batch["tokens"], remat=remat)
        return h, zero
    if cfg.family == "hybrid":
        h, _ = griffin.forward(params, cfg, batch["tokens"], remat=remat)
        return h, zero
    if cfg.family == "encdec":
        h = encdec.forward_train(params, cfg, batch["frames"],
                                 batch["tokens"], remat=remat)
        return h, zero
    raise ValueError(cfg.family)


def lm_head(params: Params, cfg: ArchConfig) -> jnp.ndarray:
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.lm_head(params, cfg)
    return params["lm_head"]


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.init_cache(cfg, batch, max_len)
    if cfg.family == "ssm":
        return rwkv6.init_state(cfg, batch)
    if cfg.family == "hybrid":
        return griffin.init_state(cfg, batch)
    if cfg.family == "encdec":
        return encdec.init_cache(cfg, batch, max_len, mem_len=4096)
    raise ValueError(cfg.family)


def kv_seq_axis(cfg: ArchConfig) -> int:
    """The sequence axis of the ``k`` and ``v`` leaves of a decode cache:
    head-major (L, B, Kh, S, Dh) in the decoder families; (L, B, S, Kh, Dh)
    in encdec, and in hybrid's local-attention ring, whose S is its
    window."""
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.KV_SEQ_AXIS
    if cfg.family in ("encdec", "hybrid"):
        return 2
    raise ValueError(f"{cfg.family} keeps no key/value cache")


def prefill(params: Params, cfg: ArchConfig, batch: Dict):
    if cfg.family in ("dense", "moe"):
        return transformer.prefill(params, cfg, batch["tokens"])
    if cfg.family == "vlm":
        return transformer.prefill(params, cfg, batch["tokens"],
                                   patches=batch["patches"])
    if cfg.family == "ssm":
        return rwkv6.prefill(params, cfg, batch["tokens"])
    if cfg.family == "hybrid":
        return griffin.prefill(params, cfg, batch["tokens"])
    if cfg.family == "encdec":
        return encdec.prefill(params, cfg, batch["frames"], batch["tokens"])
    raise ValueError(cfg.family)


def decode_step(params: Params, cfg: ArchConfig, token, pos, cache):
    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.decode_step(params, cfg, token, pos, cache)
    if cfg.family == "ssm":
        return rwkv6.decode_step(params, cfg, token, pos, cache)
    if cfg.family == "hybrid":
        return griffin.decode_step(params, cfg, token, pos, cache)
    if cfg.family == "encdec":
        return encdec.decode_step(params, cfg, token, pos, cache)
    raise ValueError(cfg.family)
