"""Decode cell: the serve driver's programs (``repro.launch.serve.build``)
in a closed loop of greedy decode steps.

Set-up makes the weights from the seed with the jitted
``api.init_params``, compiles the prefill for one group of prompts and the
decode step, prefills every prompt into one cache of ``max_len`` positions
(a group at a time, so the prefill's temporaries fit beside the cache) and
runs ``warmup_steps`` steps.  The window then dispatches decode steps
with the greedy token kept on the device, up to ``ahead_steps`` of them
beyond the oldest unfinished one, so that the chip stays fed while the
host stands still; it waits for the steps in order, and the host clock at
each wait is when that step's tokens were seen.  When its time is up it
sends nothing more, waits for every step it sent, and reads the clock
after that wait.  ``ahead_steps`` stays under what the TPU runtime holds
in flight (about 16 programs on a v5e; past that the dispatch itself
waits), so that each wait is for its own step to finish.  Every step's
logits stay on the device until the window has closed, for the check.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from cell import arch_config, prng_key, rng, sizes
from check import Request
import work


class Run:
    def __init__(self, cell, seed: int):
        from repro.launch import serve
        from repro.models import api

        t = cell.traffic
        self.cfg = cfg = arch_config(cell)
        self.s = sizes(cell.config)
        self.batch, self.prompt_len = t["batch"], t["prompt_len"]
        self.max_len, group = t["max_len"], t["prefill_group"]
        self.ahead = t["ahead_steps"]
        self.prompts = rng(seed, "prompts").integers(
            0, cfg.vocab, (self.batch, self.prompt_len), dtype=np.int32)

        self.params = jax.jit(api.init_params, static_argnums=1)(
            prng_key(seed), cfg)
        prefill, decode = serve.build(cfg, self.max_len)
        part = {"tokens": jax.ShapeDtypeStruct((group, self.prompt_len),
                                               jnp.int32)}
        prefill = prefill.lower(self.params, part).compile()
        cache = jax.jit(api.init_cache, static_argnums=(0, 1, 2))(
            cfg, self.batch, self.max_len)
        insert = jax.jit(_insert, donate_argnums=(0,))
        firsts, logits = [], []
        for b0 in range(0, self.batch, group):
            part_cache, lg, tok = prefill(
                self.params, {"tokens": self.prompts[b0:b0 + group]})
            cache = insert(cache, part_cache, jnp.asarray(b0, jnp.int32))
            firsts.append(tok)
            logits.append(lg)
            del part_cache
        self.cache = cache
        self.tok = jnp.concatenate(firsts, axis=0)
        self.decode = decode.lower(
            self.params, self.cache, self.tok,
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        self.hlo = {"jit_decode": self.decode.as_text()}
        # every step's position, the token fed to it and the logits and
        # token it served; fed[0] and logits[0] are the prefill's
        self.fed = [self.tok]
        self.logits = [jnp.concatenate(logits, axis=0)]
        self.positions = []
        self.pos = self.prompt_len
        for _ in range(t["warmup_steps"]):
            jax.block_until_ready(self._step())

    def _step(self):
        """Dispatches one decode step; returns its token, not yet ready."""
        with jax.profiler.TraceAnnotation("bench.step"):
            logits, self.cache, self.tok = self.decode(
                self.params, self.cache, self.tok,
                jnp.asarray(self.pos, jnp.int32))
        self.positions.append(self.pos)
        self.fed.append(self.tok)
        self.logits.append(logits)
        self.pos += 1
        if self.pos == self.max_len:      # the next answer to each prompt
            self.pos = self.prompt_len
        return self.tok

    def window(self, seconds: float) -> dict:
        first = len(self.positions)
        flight = collections.deque()    # tokens of the unfinished steps
        seen = []                       # when each step's tokens were seen
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            flight.append(self._step())
            if len(flight) > self.ahead:
                jax.block_until_ready(flight.popleft())
                seen.append(time.perf_counter())
        while flight:
            jax.block_until_ready(flight.popleft())
            seen.append(time.perf_counter())
        window_s = seen[-1] - t0
        gaps = np.diff([t0] + seen)     # a token's gap, step by step
        steps = self.positions[first:]
        load = {}
        for p in steps:
            load = work.add(load, work.decode_step(self.s, self.batch, p))
        tokens = self.batch * len(steps)
        return {"window_s": window_s, "work": load,
                "attempted": tokens, "failed": 0,
                "e2e": {"decode_tokens_per_s": tokens / window_s,
                        "decode_step_p95_ms":
                            1e3 * float(np.percentile(gaps, 95))}}

    def release(self):
        self.fed = np.concatenate(jax.device_get(self.fed), axis=1)
        self.logits = np.concatenate(jax.device_get(self.logits), axis=1)
        del self.params, self.cache, self.tok, self.decode

    def requests(self):
        """One request per prompt and lap: the prompt, then the tokens fed
        back in that lap; the first lap also serves the prefill's token."""
        out = []
        pos = np.asarray(self.positions)
        laps = np.cumsum(pos == self.prompt_len)
        for lap in np.unique(laps):
            idx = np.nonzero(laps == lap)[0]          # steps of this lap
            for b in range(self.batch):
                fed = self.fed[b, idx]               # token fed at pos[idx]
                served = idx + 1                     # what it produced
                rows = pos[idx]
                if lap == laps[0]:                   # the prefill's token
                    rows = np.concatenate([[self.prompt_len - 1], rows])
                    served = np.concatenate([[0], served])
                out.append(Request(
                    tokens=np.concatenate([self.prompts[b], fed]),
                    rows=rows, served=self.fed[b, served],
                    logits=self.logits[b, served]))
        return out


def _insert(cache, part, b0):
    """``part``, a prefill's cache of a group of requests, written into the
    batch's ``cache`` from request ``b0`` on: leaf by leaf, whatever the
    cache's nesting and its kinds of state, each leaf layer-first with the
    batch on axis 1."""
    return jax.tree.map(
        lambda c, p: jax.lax.dynamic_update_slice_in_dim(c, p, b0, axis=1),
        cache, part)
