"""Serving driver: batched prefill + greedy decode with a KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
      --layers 8 --batch 8 --prompt-len 512 --gen 32
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve \
      --arch gemma-2b --reduced --batch 4 --prompt-len 32 --gen 16

``--profile DIR`` traces the timed prefill and decode steps with the JAX
profiler into ``DIR``; each step is a ``StepTraceAnnotation`` (``prefill``,
``decode``) there.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, ShapeConfig
from ..models import api
from ..models.layers import scope
from ..train.step import make_prefill_step, make_serve_step
from .common import add_model_args, init_compile_cache, model_config


@scope("sample")
def _greedy(logits):
    return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)


def build(cfg: ArchConfig, max_len: int):
    """The jitted programs of a serving run: prefill straight into a decode
    cache of ``max_len`` positions, and one decode step, which donates the
    cache.  Both also return the greedy next token, so tokens stay on the
    device between steps."""
    prefill_step = make_prefill_step(cfg, max_len)
    serve_step = make_serve_step(cfg)

    def prefill(params, batch):
        cache, logits = prefill_step(params, batch)
        return cache, logits, _greedy(logits)

    def decode(params, cache, token, pos):
        logits, cache = serve_step(params, cache, token, pos)
        return logits, cache, _greedy(logits)

    return jax.jit(prefill), jax.jit(decode, donate_argnums=(1,))


def generate(cfg: ArchConfig, params, batch, *, prompt_len: int, gen: int,
             max_len: int, profile: Optional[str] = None) -> dict:
    """Greedy generation of ``gen`` tokens after the prompt ``batch``.

    Both programs are compiled before anything is timed; the prefill and
    every decode step end in ``block_until_ready``.  ``tokens`` holds the
    prefill's token and the ``gen`` decoded ones; ``first_decode_logits``
    are the logits of the first decode step (position ``prompt_len``).
    With ``profile``, the timed steps are traced into that directory."""
    if prompt_len + gen > max_len:
        raise ValueError(f"prompt {prompt_len} + gen {gen} > max_len "
                         f"{max_len}")
    prefill, decode = build(cfg, max_len)
    cache_abs, _, tok_abs = jax.eval_shape(prefill, params, batch)
    t0 = time.perf_counter()
    prefill = prefill.lower(params, batch).compile()
    t1 = time.perf_counter()
    decode = decode.lower(params, cache_abs, tok_abs,
                          jax.ShapeDtypeStruct((), jnp.int32)).compile()
    compile_s = {"prefill": t1 - t0, "decode": time.perf_counter() - t1}

    with (jax.profiler.trace(profile) if profile
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("prefill", step_num=0):
            cache, _, tok = jax.block_until_ready(prefill(params, batch))
        prefill_s = time.perf_counter() - t0

        toks, step_s, first_logits = [tok], [], None
        for i in range(gen):
            t0 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("decode", step_num=i):
                logits, cache, tok = jax.block_until_ready(
                    decode(params, cache, tok,
                           jnp.asarray(prompt_len + i, jnp.int32)))
            step_s.append(time.perf_counter() - t0)
            toks.append(tok)
            if first_logits is None:
                first_logits = logits
    return {"tokens": np.concatenate([np.asarray(t) for t in toks], axis=1),
            "first_decode_logits": np.asarray(first_logits, np.float32),
            "compile_s": compile_s, "prefill_s": prefill_s,
            "decode_step_s": step_s}


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_model_args(ap, "gemma-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="trace the timed steps into DIR")
    args = ap.parse_args(argv)

    init_compile_cache()
    cfg = model_config(args.arch, tiny=args.reduced, layers=args.layers)
    shape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")
    params = jax.jit(api.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    batch = {k: v for k, v in api.make_batch(cfg, shape).items()
             if k != "labels"}
    out = generate(cfg, params, batch, prompt_len=args.prompt_len,
                   gen=args.gen, max_len=args.prompt_len + args.gen + 8,
                   profile=args.profile)
    med = statistics.median(out["decode_step_s"])
    print(json.dumps({
        "arch": cfg.name, "layers": cfg.n_layers, "batch": args.batch,
        "compile_s": out["compile_s"], "prefill_s": out["prefill_s"],
        "median_decode_step_s": med,
        "decode_tok_per_s": args.batch / med,
        "sample_tokens": out["tokens"][0, :8].tolist(),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
