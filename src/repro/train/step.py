"""train_step / serve_step factories — the jit roots of the framework.

These are what ``launch/dryrun.py`` lowers for every (arch x shape x mesh)
cell and what ``launch/train.py`` and ``launch/serve.py`` run.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models import api
from ..optim import adamw
from .loss import chunked_xent


def loss_fn(params, cfg: ArchConfig, batch: Dict, aux_weight: float = 0.01,
            remat: bool = True) -> Tuple[jnp.ndarray, Dict]:
    h, aux = api.forward_hidden(params, cfg, batch, remat=remat)
    w = api.lm_head(params, cfg)
    nll = chunked_xent(h, w, batch["labels"])
    loss = nll + aux_weight * aux
    return loss, {"nll": nll, "aux": aux}


def make_train_step(cfg: ArchConfig, opt: adamw.AdamWConfig,
                    compress_grads: bool = False, remat: bool = True):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params", "opt", ["residual"]}.
    """

    def train_step(state, batch):
        grad_fn = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, remat=remat), has_aux=True)
        (loss, parts), grads = grad_fn(state["params"])
        if compress_grads:
            grads, new_res = adamw.compressed_grads(grads, state["residual"])
        new_p, new_opt, om = adamw.apply_updates(state["params"], grads,
                                                 state["opt"], opt)
        new_state = {"params": new_p, "opt": new_opt}
        if compress_grads:
            new_state["residual"] = new_res
        metrics = {"loss": loss, **parts, **om}
        return new_state, metrics

    return train_step


def make_serve_step(cfg: ArchConfig):
    """decode: serve_step(params, cache, token, pos) -> (logits, cache)."""

    def serve_step(params, cache, token, pos):
        return api.decode_step(params, cfg, token, pos, cache)

    return serve_step


def make_prefill_step(cfg: ArchConfig, max_len: Optional[int] = None):
    """prefill_step(params, batch) -> (cache, last-position logits).

    With ``max_len``, a key/value cache comes back padded to ``max_len``
    positions: the decode cache ``serve_step`` continues from."""
    kv_cache = cfg.family in ("dense", "moe", "vlm", "encdec")

    def prefill_step(params, batch):
        cache, logits = api.prefill(params, cfg, batch)
        if max_len is not None and kv_cache:
            cache = dict(cache)
            for name in ("k", "v"):        # (L, B, S, Kh, Dh)
                c = cache[name]
                pad = [(0, 0)] * c.ndim
                pad[2] = (0, max_len - c.shape[2])
                cache[name] = jnp.pad(c, pad)
        return cache, logits

    return prefill_step


def init_train_state(key, cfg: ArchConfig, compress_grads: bool = False):
    params = api.init_params(key, cfg)
    state = {"params": params, "opt": adamw.init_state(params)}
    if compress_grads:
        state["residual"] = adamw.init_residuals(params)
    return state
