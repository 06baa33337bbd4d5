"""Plain float32 reference of the dense GQA decoder that both
configurations are (StarCoder2, Phi-3), written from the published
descriptions in straightforward ``jax.numpy``: no kernel, no cache, no
batching, no blockwise attention.  It imports nothing of the program.

Layer: ``x += Wo . attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))``, then
``x += mlp(n2(x))`` with ``mlp(h) = Wd gelu_tanh(Wu h)`` (StarCoder2) or
``Wd (silu(Wg h) * Wu h)`` (Phi-3); RMSNorm with unit weight; causal
softmax attention over the configuration's sliding window, in which query
head ``h`` reads KV head ``h // (heads / kv_heads)``; rotary embedding on the two halves of each
head; a final RMSNorm and an untied LM head.  Departures from the
published models, shared with the program, are listed in each
configuration file (RMSNorm for StarCoder2's LayerNorm, no biases).

Weights: the configuration states the weights' type (bfloat16 for both
models), so the reference makes each weight in that type from the seed by
the same recipe the program uses (normal draws scaled by 1/sqrt(fan-in),
the embedding by 0.02, from the same split of the seed's key) and computes
with them in float32.  It makes them itself, one layer at a time, and
never reads the program's arrays.

``quant="fp8"`` is the control: every matrix product, the attention's
included, takes its operands rounded to float8 e4m3 with one scale per tensor, the precision below the
configured bfloat16 that a later change might be tempted to serve in.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q(x, quant):
    """Operand of a matrix product, in the reference's precision."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(quant)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=HIGHEST)


# ---------------------------------------------------------------------------
# weights, made from the seed
# ---------------------------------------------------------------------------

def _stored(w, s):
    """A weight as the configuration stores it, then widened to float32."""
    return w.astype(jnp.dtype(s["dtype"])).astype(F32)


def _dense(key, d_in, d_out, s):
    w = jax.random.normal(key, (d_in, d_out)) * (1.0 / math.sqrt(d_in))
    return _stored(w, s)


def model_keys(key, layers):
    """(embedding key, per-layer keys, LM-head key)."""
    k_embed, k_layers, k_head, _ = jax.random.split(key, 4)
    return k_embed, jax.random.split(k_layers, layers), k_head


def layer_weights(key, s):
    k_attn, k_mlp = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    d, hd = s["d_model"], s["head_dim"]
    w = {"wq": _dense(kq, d, s["heads"] * hd, s),
         "wk": _dense(kk, d, s["kv_heads"] * hd, s),
         "wv": _dense(kv, d, s["kv_heads"] * hd, s),
         "wo": _dense(ko, s["heads"] * hd, d, s)}
    kg, ku, kd = jax.random.split(k_mlp, 3)
    w["wu"] = _dense(ku, d, s["d_ff"], s)
    w["wd"] = _dense(kd, s["d_ff"], d, s)
    if s["act"] != "gelu":
        w["wg"] = _dense(kg, d, s["d_ff"], s)
    return w


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) *
                                     (x + 0.044715 * x ** 3)))


def rope_tables(positions, head_dim, theta):
    """cos and sin of the rotary angles, worked out in float64."""
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rope(x, cos, sin):
    """x: (T, H, Dh); rotate_half on the two halves of each head."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


QUERY_BLOCK = 1024


def attend(q, k, v, window, quant):
    """Causal softmax attention of one sequence, query rows a block at a
    time so the (heads, rows, keys) scores stay small.  q, k, v: (T, H,
    hd); with ``window`` > 0, row ``i`` reads keys ``j`` with
    ``i - window < j <= i``."""
    T, hd = q.shape[0], q.shape[-1]
    j = jnp.arange(T)
    k, v = _q(k, quant), _q(v, quant)
    out = []
    for r0 in range(0, T, QUERY_BLOCK):
        i = jnp.arange(r0, min(T, r0 + QUERY_BLOCK))
        keep = j[None, :] <= i[:, None]
        if window:
            keep &= j[None, :] > i[:, None] - window
        scores = jnp.einsum("thd,shd->hts", _q(q[i], quant), k,
                            precision=HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", _q(p, quant), v,
                              precision=HIGHEST))
    return jnp.concatenate(out, axis=0)


@partial(jax.jit, static_argnames=("s", "quant"))
def layer(w, x, cos, sin, s, quant=None):
    """One decoder layer with weights ``w`` over one sequence ``x`` (T, d)."""
    s = dict(s)
    T = x.shape[0]
    H, Kh, hd = s["heads"], s["kv_heads"], s["head_dim"]
    h = rms_norm(x, s["eps"])
    q = rope(_mm(h, w["wq"], quant).reshape(T, H, hd), cos, sin)
    k = rope(_mm(h, w["wk"], quant).reshape(T, Kh, hd), cos, sin)
    v = _mm(h, w["wv"], quant).reshape(T, Kh, hd)
    k = jnp.repeat(k, H // Kh, axis=1)
    v = jnp.repeat(v, H // Kh, axis=1)
    o = attend(q, k, v, s["window"], quant)
    x = x + _mm(o.reshape(T, H * hd), w["wo"], quant)
    h = rms_norm(x, s["eps"])
    if s["act"] == "gelu":
        m = gelu_tanh(_mm(h, w["wu"], quant))
    else:
        m = jax.nn.silu(_mm(h, w["wg"], quant)) * _mm(h, w["wu"], quant)
    return x + _mm(m, w["wd"], quant)


@partial(jax.jit, static_argnames=("s",))
def make_layer(key, s):
    return layer_weights(key, dict(s))


@partial(jax.jit, static_argnames=("s",))
def make_embed(key, s):
    s = dict(s)
    return _stored(jax.random.normal(key, (s["vocab"], s["d_model"])) * 0.02,
                   s)


@partial(jax.jit, static_argnames=("s",))
def make_head(key, s):
    s = dict(s)
    return _dense(key, s["d_model"], s["vocab"], s)


@partial(jax.jit, static_argnames=("s", "quant"))
def head(w, x, rows, s, quant=None):
    """Logits (n, vocab) of the rows ``rows`` of ``x``."""
    return _mm(rms_norm(x[rows], dict(s)["eps"]), w, quant)


def bucket(n: int, step: int = 512) -> int:
    """Sequences are padded at the end to a multiple of ``step`` so a
    handful of compiled programs serve every length; causal attention
    keeps the padding out of every real row."""
    return -(-n // step) * step


def row_logits(s: dict, key, seqs, quant=None):
    """Run the model over each sequence of ``seqs``, a list of
    ``(tokens, rows)``, and read the logits at its ``rows``.  Layer by
    layer: each layer's weights are made once and applied to every
    sequence before the next layer's are made.  Returns one numpy array
    (len(rows), vocab) per sequence."""
    frozen = tuple(sorted(s.items()))
    k_embed, k_layers, k_head = model_keys(key, s["layers"])
    xs, tables = [], []
    with jax.default_matmul_precision("highest"):
        table = make_embed(k_embed, frozen)
        for tokens, _ in seqs:
            padded = np.zeros(bucket(len(tokens)), np.int32)
            padded[:len(tokens)] = tokens
            xs.append(table[jnp.asarray(padded)])
            tables.append(rope_tables(np.arange(len(padded)), s["head_dim"],
                                      s["rope_theta"]))
        del table
        for i in range(s["layers"]):
            w = make_layer(k_layers[i], frozen)
            xs = [layer(w, x, cos, sin, frozen, quant)
                  for x, (cos, sin) in zip(xs, tables)]
            del w
        w = make_head(k_head, frozen)
        return [np.asarray(head(w, x, jnp.asarray(rows, jnp.int32), frozen,
                                quant))
                for x, (_, rows) in zip(xs, seqs)]
