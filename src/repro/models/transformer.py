"""Decoder-only transformer family: dense GQA, MoE, and VLM-backbone.

Covers llama3-8b, phi3-medium-14b, starcoder2-7b, gemma-2b (dense),
grok-1-314b, moonshot-v1-16b-a3b (MoE), internvl2-1b (vision-stub prefix).

Layer parameters are stacked on a leading L axis and executed with
``jax.lax.scan`` (+ ``jax.checkpoint`` for train) so compile time and HLO
size are depth-independent — essential for the 512-device dry-run.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers as L
from ..distributed import hints

Params = Dict[str, Any]


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ArchConfig) -> Params:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p: Params = {
        "ln1": jnp.zeros((cfg.d_model,), dt),
        "ln2": jnp.zeros((cfg.d_model,), dt),
        "attn": L.gqa_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                           cfg.hd, dt),
    }
    if cfg.moe:
        p["moe"] = L.moe_init(k2, cfg.d_model, cfg.moe.num_experts,
                              cfg.moe.d_ff_expert, dt)
    else:
        p["mlp"] = L.glu_mlp_init(k2, cfg.d_model, cfg.d_ff, dt, cfg.act)
    return p


def init_params(key, cfg: ArchConfig) -> Params:
    dt = _dtype(cfg)
    ke, kl, kh, kf = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    p: Params = {
        "embed": L.embed_init(ke, cfg.vocab, cfg.d_model, dt),
        "layers": stacked,
        "norm_f": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(kh, cfg.d_model, cfg.vocab, dt)
    if cfg.frontend == "vision":
        # connector from stub patch embeddings (at d_model) into the LM
        p["connector"] = L.dense_init(kf, cfg.d_model, cfg.d_model, dt)
    return p


def lm_head(params: Params, cfg: ArchConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

def layer_fwd(p: Params, x: jnp.ndarray, cfg: ArchConfig,
              positions: jnp.ndarray, *, causal: bool = True,
              kv_override: Optional[Tuple] = None,
              kv_len=None, q_offset=0
              ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray],
                         jnp.ndarray]:
    """Pre-norm block.  Returns (x_out, (k, v) of THIS segment, aux_loss)."""
    x = hints.constrain(x, "dp", "sp", None)
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    q, k, v = L.gqa_project(h, p["attn"], cfg.n_heads, cfg.n_kv_heads,
                            cfg.hd, positions, cfg.rope_theta)
    if kv_override is not None:
        k_all, v_all = kv_override
    else:
        k_all, v_all = k, v
    o = L.attention(q, k_all, v_all, causal=causal, q_offset=q_offset,
                    window=cfg.window, kv_len=kv_len)
    x = _attn_out(x, o, p["attn"]["wo"])
    h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe:
        m, aux = L.moe_mlp(h2, p["moe"], cfg.moe.top_k,
                           cfg.moe.capacity_factor,
                           act=cfg.act,
                           group_size=cfg.moe.group_size,
                           expert_sharding=cfg.moe.sharding)
    else:
        m = L.glu_mlp(h2, p["mlp"], cfg.act)
    return _residual_mlp(x, m), (k, v), aux


# The residual adds sit in the scope of the part they close: XLA fuses each
# into the matmul that feeds it, and a fused op is read by its root's scope.
@L.scope("attn_out")
def _attn_out(x, o, wo):
    return x + o.reshape(*o.shape[:2], -1) @ wo


@L.scope("mlp")
def _residual_mlp(x, m):
    return x + m


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------

@L.scope("embed")
def embed_inputs(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
                 patches: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = params["embed"][tokens]
    if cfg.family == "dense" and cfg.tie_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)   # gemma scale
    if patches is not None:
        pref = patches.astype(x.dtype) @ params["connector"]
        x = jnp.concatenate([pref, x], axis=1)
    return x


def forward(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
            patches: Optional[jnp.ndarray] = None, *,
            collect_cache: bool = False, remat: bool = True
            ) -> Tuple[jnp.ndarray, Optional[Tuple], jnp.ndarray]:
    """Returns (hidden (B,S,D), optional stacked (k, v) cache, aux_loss)."""
    x = embed_inputs(params, cfg, tokens, patches)
    S = x.shape[1]
    positions = jnp.arange(S)

    def body(carry, pl):
        x, aux = carry
        x, (k, v), a = layer_fwd(pl, x, cfg, positions)
        ys = (k, v) if collect_cache else None
        return (x, aux + a), ys

    fn = jax.checkpoint(body,
                        policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body
    (x, aux), kv = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                                params["layers"])
    x = L.rmsnorm(x, params["norm_f"], cfg.norm_eps)
    return x, kv, aux


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    dt = _dtype(cfg)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def prefill(params: Params, cfg: ArchConfig, tokens: jnp.ndarray,
            patches: Optional[jnp.ndarray] = None
            ) -> Tuple[Params, jnp.ndarray]:
    """Run the prompt, return (cache, last-token logits)."""
    x, kv, _ = forward(params, cfg, tokens, patches, collect_cache=True,
                       remat=False)
    with L.scope("lm_head"):
        logits = x[:, -1:] @ lm_head(params, cfg)
    return {"k": kv[0], "v": kv[1]}, logits


def decode_step(params: Params, cfg: ArchConfig, token: jnp.ndarray,
                pos, cache: Params) -> Tuple[jnp.ndarray, Params]:
    """One-token decode against a KV cache.

    token: (B, 1) int32; pos: scalar int32 — current length (same for the
    batch; per-request lengths are handled by the serving layer's bucketing).
    The layer scan returns only the step's new K/V per layer; the cache is
    written once, in place when donated, at ``pos`` after the loop.
    """
    x = embed_inputs(params, cfg, token)
    positions = pos + jnp.arange(1)

    def body(carry, pl):
        x, i = carry
        # fetching the layer's K/V from the stacked cache is the attention's
        # traffic: the compiler stages it into on-chip memory for the dots
        with L.scope("attention_kernel"):
            kc, vc = (jax.lax.dynamic_index_in_dim(cache[name], i,
                                                   keepdims=False)
                      for name in ("k", "v"))
        kc = hints.constrain(kc, "dp", "model", None, None)
        vc = hints.constrain(vc, "dp", "model", None, None)
        h = L.rmsnorm(x, pl["ln1"], cfg.norm_eps)
        q, k, v = L.gqa_project(h, pl["attn"], cfg.n_heads, cfg.n_kv_heads,
                                cfg.hd, positions, cfg.rope_theta)
        k, v = k.astype(kc.dtype), v.astype(vc.dtype)
        with L.scope("kv_cache_write"):
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, axis=1)
        o = L.attention(q, kc, vc, causal=False, q_offset=pos,
                        window=cfg.window, kv_len=pos + 1)
        x = _attn_out(x, o, pl["attn"]["wo"])
        h2 = L.rmsnorm(x, pl["ln2"], cfg.norm_eps)
        if cfg.moe:
            m, _ = L.moe_mlp(h2, pl["moe"], cfg.moe.top_k,
                             cfg.moe.capacity_factor, act=cfg.act,
                             group_size=cfg.moe.group_size,
                             expert_sharding=cfg.moe.sharding)
        else:
            m = L.glu_mlp(h2, pl["mlp"], cfg.act)
        return (_residual_mlp(x, m), i + 1), (k, v)

    # (L, B, 1, Kh, Dh) as ys: stacking each layer's whole updated cache
    # would copy all of it every step
    (x, _), (k_new, v_new) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.int32)), params["layers"])
    with L.scope("kv_cache_write"):
        cache = {name: jax.lax.dynamic_update_slice_in_dim(cache[name], new,
                                                           pos, axis=2)
                 for name, new in (("k", k_new), ("v", v_new))}
    x = L.rmsnorm(x, params["norm_f"], cfg.norm_eps)
    with L.scope("lm_head"):
        logits = x @ lm_head(params, cfg)
    return logits, cache
