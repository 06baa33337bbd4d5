"""Prefill cell: the serve driver's prefill program
(``repro.launch.serve.build``) in a closed loop of calls, each on fresh
prompts.

Set-up makes the weights from the seed with the jitted
``api.init_params``, compiles the prefill for the cell's batch and prompt
length and runs ``warmup_calls`` calls.  The window then calls it as the
serve driver does, with one ``block_until_ready`` per call; each call's
prompts are drawn from the seed as it is made, and its cache is dropped
once it is ready; its last-row logits stay on the device until the window
has closed, for the check.
"""

from __future__ import annotations

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
        self.gen = rng(seed, "prompts")
        self.params = jax.jit(api.init_params, static_argnums=1)(
            prng_key(seed), cfg)
        prefill, _ = serve.build(cfg, t["max_len"])
        spec = {"tokens": jax.ShapeDtypeStruct(
            (self.batch, self.prompt_len), jnp.int32)}
        self.prefill = prefill.lower(self.params, spec).compile()
        self.hlo = {"jit_prefill": self.prefill.as_text()}
        self.prompts, self.served, self.logits = [], [], []
        for _ in range(t["warmup_calls"]):
            self._call()
        jax.block_until_ready(self.served[-1])

    def _call(self):
        prompts = self.gen.integers(0, self.cfg.vocab,
                                    (self.batch, self.prompt_len),
                                    dtype=np.int32)
        with jax.profiler.TraceAnnotation("bench.step"):
            cache, logits, tok = jax.block_until_ready(
                self.prefill(self.params, {"tokens": prompts}))
        del cache
        self.prompts.append(prompts)
        self.served.append(tok)
        self.logits.append(logits)

    def window(self, seconds: float) -> dict:
        first = len(self.served)
        t0 = time.perf_counter()
        while True:
            self._call()
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        window_s = t1 - t0
        calls = len(self.served) - first
        one = work.prefill(self.s, self.batch, self.prompt_len)
        tokens = calls * self.batch * self.prompt_len
        return {"window_s": window_s,
                "work": {k: v * calls for k, v in one.items()},
                "attempted": calls * self.batch, "failed": 0,
                "e2e": {"prefill_tokens_per_s": tokens / window_s}}

    def release(self):
        self.served = np.concatenate(jax.device_get(self.served), axis=0)
        self.logits = np.concatenate(jax.device_get(self.logits), axis=0)
        del self.params, self.prefill

    def requests(self):
        """One request per prompt; its answer is the greedy first token,
        served at the prompt's last row."""
        prompts = np.concatenate(self.prompts, axis=0)
        last = np.asarray([self.prompt_len - 1])
        return [Request(tokens=p, rows=last, served=self.served[i],
                        logits=self.logits[i])
                for i, p in enumerate(prompts)]
