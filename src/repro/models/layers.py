"""Shared JAX building blocks for the model zoo.

Pure functions over explicit parameter pytrees (dicts of jnp arrays) — no
framework dependency.  Attention is blockwise (online softmax over KV
chunks) so the S x S score matrix is never materialized; on TPU the Pallas
flash-attention kernel (src/repro/kernels) implements the same contract.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..distributed import hints

Params = Dict[str, jnp.ndarray]

# Named scopes of the decoder's parts.  ``jax.named_scope`` puts each into
# the op_name of every HLO instruction built inside it, so a device trace
# of the compiled program can be read part by part.  What lies under none
# of them is the work the programs do between the parts: the layer scan's
# slicing and stacking of the stacked cache, the prefill's cache padding.
SCOPES = (
    "embed",             # token embedding lookup
    "norm",              # RMSNorm
    "qkv_proj",          # Q, K, V projections, RoPE included
    "attention_kernel",  # softmax(QK^T)V: the part a flash kernel replaces
    "attn_out",          # output projection and its residual add
    "kv_cache_write",    # the decode step's write of the new K and V
    "mlp",               # dense or expert MLP and its residual add
    "lm_head",           # logits
    "sample",            # greedy choice of the next token
)


def scope(name: str):
    """``jax.named_scope`` for one of ``SCOPES``; a context manager, or a
    decorator that opens the scope around each call."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of {SCOPES}")
    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

@scope("norm")
def rmsnorm(x: jnp.ndarray, g: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (n * (1.0 + g.astype(jnp.float32))).astype(dt)


@scope("mlp")
def glu_mlp(x: jnp.ndarray, p: Params, act: str) -> jnp.ndarray:
    """SwiGLU / GeGLU: (act(x W_g) * (x W_u)) W_d — or, when the params
    carry no gate matrix ("gelu" archs like StarCoder2), a plain 2-matrix
    act(x W_u) W_d."""
    u = x @ p["wu"]
    if "wg" not in p:
        if u.ndim == 3:
            u = hints.constrain(u, "dp", None, "model")
        return jax.nn.gelu(u) @ p["wd"]
    g = x @ p["wg"]
    h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    if h.ndim == 3:
        h = hints.constrain(h, "dp", None, "model")
    return h @ p["wd"]


def glu_mlp_init(key, d: int, f: int, dtype, act: str = "swiglu") -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wu": dense_init(k2, d, f, dtype),
         "wd": dense_init(k3, f, d, dtype)}
    if act != "gelu":
        p["wg"] = dense_init(k1, d, f, dtype)
    return p


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, H, Dh); positions: (T,) or broadcastable."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (T, half)
    cos = jnp.cos(ang)[..., None, :]                            # (T, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    return jnp.concatenate([
        (x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin),
        (x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin),
    ], axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# blockwise attention (the jnp reference contract for the Pallas kernel)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, q_offset=0, window: int = 0,
              kv_len=None, block: int = 1024) -> jnp.ndarray:
    """Online-softmax attention.

    q: (B, T, H, Dh);  k, v: (B, S, Kh, Dh) with H % Kh == 0 (GQA).
    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``window`` > 0: sliding-window (local) attention.
    ``kv_len``: scalar/array — keys at positions >= kv_len are masked
    (partially-filled cache).
    Never materializes (T, S) for S > block: scans KV blocks.
    """
    B, T, H, Dh = q.shape
    S, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    # decode (tiny T, long cache): keep the cache SEQUENCE-sharded and
    # compute partial softmax per shard — resharding the cache to head
    # sharding would all-gather S x Kh x Dh every step (measured: 64 GB
    # per decode step on llama3-8b/decode_32k before this branch existed)
    if T <= 16 and S >= 4096:
        return _decode_attention(q, k, v, causal=causal, q_offset=q_offset,
                                 window=window, kv_len=kv_len)
    # sharding: heads over 'model' when divisible (Megatron attention);
    # otherwise fall back to sequence parallelism — shard the query rows
    # and let K/V be gathered per layer (cheap relative to replicating
    # the whole attention compute 'model'-fold)
    if hints.divides("model", H):
        if not hints.divides("model", Kh):
            # GQA with kv_heads < TP degree: duplicate each KV head so the
            # head dim shards cleanly (MaxText-style) — removes the KV
            # all-gather + replicated-KV gradient all-reduce entirely at
            # the cost of r-fold duplicate KV projections
            import math as _m
            msize = hints.MESH.shape["model"]
            r = msize // _m.gcd(Kh, msize)
            if r > 1 and G % r == 0:
                k = jnp.repeat(k, r, axis=2)
                v = jnp.repeat(v, r, axis=2)
                Kh, G = Kh * r, G // r
        q = hints.constrain(q, "dp", None, "model", None)
        k = hints.constrain(k, "dp", None, "model", None)
        v = hints.constrain(v, "dp", None, "model", None)
        head_sharded = True
    else:
        q = hints.constrain(q, "dp", "spm", None, None)
        k = hints.constrain(k, "dp", None, None, None)
        v = hints.constrain(v, "dp", None, None, None)
        head_sharded = False
    return _attention_inner(q, k, v, causal=causal, q_offset=q_offset,
                            window=window, kv_len=kv_len, block=block,
                            head_sharded=head_sharded)


@scope("attention_kernel")
def _decode_attention(q, k, v, *, causal, q_offset, window, kv_len):
    """A few queries against a long cache, in one pass over every cached
    position."""
    B, T, H, Dh = q.shape
    S, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    q = hints.constrain(q, "dp", None, None, None)
    k = hints.constrain(k, "dp", "spm", None, None)
    v = hints.constrain(v, "dp", "spm", None, None)
    scale = 1.0 / math.sqrt(Dh)
    qs = (q * scale).reshape(B, T, Kh, G, Dh)
    s = jnp.einsum("btkgd,bskd->bkgts", qs, k,
                   preferred_element_type=jnp.float32)
    pos_k = jnp.arange(S)
    q_pos = q_offset + jnp.arange(T)
    mask = jnp.ones((T, S), dtype=bool)
    if causal:
        mask = mask & (pos_k[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (pos_k[None, :] > q_pos[:, None] - window)
    if kv_len is not None:
        mask = mask & (pos_k[None, :] < kv_len)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgts,bskd->btkgd", (p / l).astype(v.dtype), v)
    return o.reshape(B, T, H, Dh)


@scope("attention_kernel")
def _attention_inner(q, k, v, *, causal, q_offset, window, kv_len, block,
                     head_sharded):
    """The part the Pallas flash kernel replaces on TPU — wrapped in a
    named scope so the HLO analyzer can attribute its traffic."""
    B, T, H, Dh = q.shape
    S, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    scale = 1.0 / math.sqrt(Dh)
    qs = (q * scale).reshape(B, T, Kh, G, Dh)
    q_pos = q_offset + jnp.arange(T)

    def block_scores(kb, pos_k):
        # kb: (B, Sb, Kh, Dh) -> scores (B, Kh, G, T, Sb), fp32
        s = jnp.einsum("btkgd,bskd->bkgts", qs, kb,
                       preferred_element_type=jnp.float32)
        mask = jnp.ones((T, kb.shape[1]), dtype=bool)
        if causal:
            mask &= pos_k[None, :] <= q_pos[:, None]
        if window:
            mask &= pos_k[None, :] > q_pos[:, None] - window
        if kv_len is not None:
            mask &= pos_k[None, :] < kv_len
        return jnp.where(mask[None, None, None], s, NEG_INF)

    if S <= 2 * block:
        pos_k = jnp.arange(S)
        s = block_scores(k, pos_k)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jax.lax.stop_gradient(m))
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgts,bskd->btkgd", (p / l).astype(v.dtype), v)
        o = o.reshape(B, T, H, Dh)
        return hints.constrain(o, "dp", None, "model", None) \
            if head_sharded else hints.constrain(o, "dp", "spm", None, None)

    nb = (S + block - 1) // block
    pad = nb * block - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nb, block, Kh, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, Kh, Dh).transpose(1, 0, 2, 3, 4)
    eff_len = kv_len if kv_len is not None else S

    def step(carry, blk):
        m, l, acc, i = carry
        kblk, vblk = blk
        if head_sharded:
            kblk = hints.constrain(kblk, "dp", None, "model", None)
            vblk = hints.constrain(vblk, "dp", None, "model", None)
        pos_k = i * block + jnp.arange(block)
        s = block_scores(kblk, jnp.where(pos_k < eff_len, pos_k, 1 << 30))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr[..., 0][..., None] + jnp.einsum(
            "bkgts,bskd->bkgtd", p.astype(vblk.dtype), vblk
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new, i + 1), None

    m0 = jnp.full((B, Kh, G, T, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kh, G, T, 1), jnp.float32)
    a0 = jnp.zeros((B, Kh, G, T, Dh), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, 0), (kb, vb))
    o = (acc / l).astype(q.dtype)                     # (B, Kh, G, T, Dh)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh)
    return hints.constrain(o, "dp", None, "model", None) \
        if head_sharded else hints.constrain(o, "dp", "spm", None, None)


def gqa_init(key, d: int, n_heads: int, n_kv: int, hd: int, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"wq": dense_init(k1, d, n_heads * hd, dtype),
            "wk": dense_init(k2, d, n_kv * hd, dtype),
            "wv": dense_init(k3, d, n_kv * hd, dtype),
            "wo": dense_init(k4, n_heads * hd, d, dtype)}


@scope("qkv_proj")
def gqa_project(x: jnp.ndarray, p: Params, n_heads: int, n_kv: int, hd: int,
                positions, theta: float, use_rope: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, T, _ = x.shape
    q = (x @ p["wq"]).reshape(B, T, n_heads, hd)
    k = (x @ p["wk"]).reshape(B, T, n_kv, hd)
    v = (x @ p["wv"]).reshape(B, T, n_kv, hd)
    if use_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity-based dense dispatch)
# ---------------------------------------------------------------------------

def moe_init(key, d: int, num_experts: int, d_ff: int, dtype) -> Params:
    kr, kg, ku, kd = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": dense_init(kr, d, num_experts, dtype),
        "wg": (jax.random.normal(kg, (num_experts, d, d_ff)) * s_in
               ).astype(dtype),
        "wu": (jax.random.normal(ku, (num_experts, d, d_ff)) * s_in
               ).astype(dtype),
        "wd": (jax.random.normal(kd, (num_experts, d_ff, d)) * s_out
               ).astype(dtype),
    }


@scope("mlp")
def moe_mlp(x: jnp.ndarray, p: Params, top_k: int, capacity_factor: float,
            act: str = "swiglu", group_size: int = 512,
            expert_sharding: str = "tp") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed MoE, GShard-style grouped capacity dispatch.

    Tokens are split into groups of ``group_size``; each group dispatches
    its tokens to per-expert buffers of capacity ``cf * k * group / E`` via
    one-hot contractions (GSPMD-canonical: the group axis shards over
    'data', the expert axis over 'model' for "ep" sharding; over-capacity
    tokens are dropped as in GShard).  Returns (output, aux_loss).
    """
    B, S, D = x.shape
    E = p["router"].shape[-1]
    T = B * S
    Sg = min(group_size, T)
    G = T // Sg
    assert G * Sg == T, f"tokens {T} not divisible by group {Sg}"
    xg = x.reshape(G, Sg, D)
    logits = jnp.einsum("gsd,de->gse", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (G, Sg, E)
    gate_vals, idx = jax.lax.top_k(probs, top_k)             # (G, Sg, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    # decode-sized groups: give every assignment a slot (no drops)
    cap = min(Sg * top_k,
              max(top_k, int(capacity_factor * top_k * Sg / E) + 1))

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # (G, Sg, k, E)
    flat = onehot.reshape(G, Sg * top_k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                    # (G, Sg*k, E)
    pos_in_e = jnp.sum(pos * flat, axis=-1).reshape(G, Sg, top_k)
    keep = pos_in_e < cap
    gate_vals = gate_vals * keep

    pos_oh = jax.nn.one_hot(jnp.where(keep, pos_in_e, cap), cap + 1,
                            dtype=x.dtype)[..., :cap]        # (G, Sg, k, cap)
    # dispatch (G, Sg, E, cap): a token occupies each expert at most once.
    # one-hots are piecewise-constant: stop_gradient prevents XLA from
    # materializing (and all-reducing) their identically-zero cotangents —
    # measured 2.6 TB/device of f32 all-reduce on grok-1 before this
    disp = jax.lax.stop_gradient(
        jnp.einsum("gske,gskc->gsec", onehot.astype(x.dtype), pos_oh))
    xin = jnp.einsum("gsd,gsec->gecd", xg, disp)             # (G, E, cap, D)
    e_ax = "model" if expert_sharding == "ep" else None
    f_ax = None if expert_sharding == "ep" else "model"
    xin = hints.constrain(xin, "dp", e_ax, None, None)       # EP: all-to-all
    g = jnp.einsum("gecd,edf->gecf", xin, p["wg"])
    u = jnp.einsum("gecd,edf->gecf", xin, p["wu"])
    g = hints.constrain(g, "dp", e_ax, None, f_ax)
    h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    out_e = jnp.einsum("gecf,efd->gecd", h, p["wd"])         # (G, E, cap, D)
    # NOTE: pinning this psum point to replicated was tried and REFUTED
    # (collective 174 -> 189 s on grok-1; see EXPERIMENTS.md §Perf) —
    # UNCONSTRAINED lets the solver place the reduction better
    out_e = hints.constrain(out_e, "dp", e_ax, None, None)
    comb = jnp.einsum("gsec,gske,gsk->gsec", disp,
                      jax.lax.stop_gradient(onehot.astype(x.dtype)),
                      gate_vals.astype(x.dtype))
    out = jnp.einsum("gecd,gsec->gsd", out_e, comb)
    # load-balancing aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                       axis=(0, 1))
    router_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(density * router_prob)
    return out.reshape(B, S, D), aux
