"""The dense GQA decoder (StarCoder2, Phi-3): its sizes, the program's
``ArchConfig`` for it, its plain float32 reference, the check of the
program's weight recipe, and the work of its prefill and decode step.

The reference is written from the published descriptions in
straightforward ``jax.numpy``: no kernel, no cache, no batching, no
blockwise attention.  It imports nothing of the program; only
``arch_config`` does, to hand the program its model.

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

from work import BF16, pairs

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
# the configuration's sizes, and the model as the program is given it
# ---------------------------------------------------------------------------

ACTIVATIONS = {"gelu_pytorch_tanh": "gelu", "silu": "swiglu"}


def sizes(config: dict) -> dict:
    """The configuration's sizes under plain names; ``window`` is the
    sliding window (0: none)."""
    eps = config.get("rms_norm_eps", config.get("norm_epsilon"))
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {"family": config["family"],
            "layers": int(config["num_hidden_layers"]), "d_model": d,
            "heads": h,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim", d // h),
            "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "act": ACTIVATIONS[config["hidden_act"]],
            "rope_theta": float(config["rope_theta"]), "eps": float(eps),
            "window": int(config.get("sliding_window") or 0),
            "dtype": config["torch_dtype"]}


def arch_config(config: dict, name: str):
    """The program's ``ArchConfig`` for this model at the configuration's
    depth."""
    from repro.configs.base import ArchConfig
    s = sizes(config)
    return ArchConfig(
        name=name, family="dense", n_layers=s["layers"],
        d_model=s["d_model"], n_heads=s["heads"], n_kv_heads=s["kv_heads"],
        d_ff=s["d_ff"], vocab=s["vocab"], head_dim=s["head_dim"],
        act=s["act"], norm_eps=s["eps"], rope_theta=s["rope_theta"],
        window=s["window"], dtype=s["dtype"])


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


# ---------------------------------------------------------------------------
# the program's weight recipe
# ---------------------------------------------------------------------------

def witness(params, key, s: dict) -> float:
    """Largest difference between the program's layer-0 weights
    (``params``) and the reference's, both widened to float32 (0 when the
    recipe agrees)."""
    _, layer_keys, _ = model_keys(key, s["layers"])
    ref = layer_weights(layer_keys[0], s)
    prog = params["layers"]
    worst = 0.0
    for group in ("attn", "mlp"):
        for name, leaf in prog[group].items():
            got = np.asarray(leaf[0]).astype(np.float32)
            worst = max(worst, float(np.abs(got - np.asarray(ref[name]))
                                     .max()))
    return worst


# ---------------------------------------------------------------------------
# work, from the shapes (see ``work.py`` for what is counted)
# ---------------------------------------------------------------------------

def layer_params(s) -> int:
    """Matrix parameters of one layer (the norms are negligible)."""
    d, hd = s["d_model"], s["head_dim"]
    attn = 2 * d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd
    mlp = (2 if s["act"] == "gelu" else 3) * d * s["d_ff"]
    return attn + mlp


def head_params(s) -> int:
    return s["d_model"] * s["vocab"]


def attention_flops(s, batch: int, queries: int, keys_before: int = 0
                    ) -> float:
    """QK^T and PV over every layer for the ``pairs`` of ``queries`` new
    positions."""
    return 4.0 * s["layers"] * batch * s["heads"] * s["head_dim"] * \
        pairs(queries, keys_before, s["window"])


def attention_bytes(s, batch: int, queries: int, keys: int) -> float:
    """Q and O of the new positions, K and V of every key, per layer."""
    q = batch * queries * s["heads"] * s["head_dim"] * BF16
    kv = batch * keys * s["kv_heads"] * s["head_dim"] * BF16
    return float(s["layers"] * (2 * q + 2 * kv))


def prefill(s, batch: int, prompt: int) -> dict:
    """One prefill call: every layer over every prompt position, the LM
    head over the last position only (what the program returns)."""
    dense = 2.0 * batch * prompt * s["layers"] * layer_params(s) + \
        2.0 * batch * head_params(s)
    att = attention_flops(s, batch, prompt)
    weights = (s["layers"] * layer_params(s) + head_params(s)) * BF16
    kv_out = 2 * s["layers"] * batch * prompt * s["kv_heads"] * \
        s["head_dim"] * BF16
    return {"flops": dense + att, "bytes": float(weights + kv_out),
            "attention_flops": att,
            "attention_bytes": attention_bytes(s, batch, prompt, prompt)}


def decode_step(s, batch: int, pos: int) -> dict:
    """One decode step at position ``pos``: one token per request that
    attends to its live keys, ``pos + 1`` or the window."""
    live = pairs(1, pos, s["window"])
    dense = 2.0 * batch * (s["layers"] * layer_params(s) + head_params(s))
    att = attention_flops(s, batch, 1, pos)
    weights = (s["layers"] * layer_params(s) + head_params(s)) * BF16
    embed_rows = batch * s["d_model"] * BF16
    kv_read = 2 * s["layers"] * batch * live * s["kv_heads"] * \
        s["head_dim"] * BF16
    kv_write = 2 * s["layers"] * batch * s["kv_heads"] * s["head_dim"] * BF16
    return {"flops": dense + att,
            "bytes": float(weights + embed_rows + kv_read + kv_write),
            "attention_flops": att,
            "attention_bytes": attention_bytes(s, batch, 1, live)}
