"""End-to-end training driver.

Runs any registered architecture at its published widths (``--layers``
cuts depth only) or at the tiny CPU-test widths (``--reduced``): sharded
state, data pipeline with prefetch + deterministic restart, checkpointing
with keep-N rotation, elastic restore onto a different mesh, optional int8
gradient compression.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b \
      --layers 2 --steps 60 --batch 8 --seq 512 --lr 1e-4
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.train \
      --arch llama3-8b --reduced --steps 200 --batch 8 --seq 128 \
      --ckpt-dir ckpt

``--profile DIR`` traces the timed steps with the JAX profiler into
``DIR``; each step is a ``StepTraceAnnotation`` (``train``) there.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import time
from functools import partial
from typing import Optional

import jax

from ..configs.base import ArchConfig, ShapeConfig
from ..data.pipeline import PipelineConfig, TokenPipeline
from ..distributed import hints
from ..distributed import sharding as shard
from ..distributed.checkpoint import CheckpointManager
from ..models import api
from ..optim.adamw import AdamWConfig
from ..train.step import init_train_state, make_train_step
from .common import add_model_args, init_compile_cache, model_config
from .mesh import make_device_mesh


def build(cfg: ArchConfig, mesh, shape: ShapeConfig, *, lr: float,
          compress_grads: bool = False):
    """The initial train state, placed on ``mesh`` by the sharding plan as
    it is created, and the jitted step that donates it.

    Returns ``(state, step_fn, state_shardings)``.  Call under
    ``hints.use_mesh(mesh)`` so the model's sharding hints see the mesh."""
    init = partial(init_train_state, cfg=cfg, compress_grads=compress_grads)
    key = jax.random.PRNGKey(0)
    st_sh = shard.to_named(
        shard.state_specs(jax.eval_shape(init, key), cfg, mesh), mesh)
    b_sh = shard.to_named(
        shard.batch_specs(api.input_specs(cfg, shape), cfg, mesh), mesh)
    state = jax.jit(init, out_shardings=st_sh)(key)
    step_fn = jax.jit(
        make_train_step(cfg, AdamWConfig(lr=lr),
                        compress_grads=compress_grads),
        in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
        donate_argnums=(0,))
    return state, step_fn, st_sh


def train(cfg: ArchConfig, mesh, shape: ShapeConfig, *, steps: int,
          lr: float, compress_grads: bool = False, ckpt=None,
          ckpt_every: int = 25, resume: bool = False, log_every: int = 10,
          profile: Optional[str] = None):
    """Train for ``steps`` steps (counting from a resumed checkpoint).

    The step is compiled before the first one runs, so ``compile_s`` and
    the per-step times (``step_s``, each ending in ``block_until_ready``)
    stay apart.  With ``profile``, the steps are traced into that
    directory.  Returns ``(state, summary)``."""
    with hints.use_mesh(mesh):
        state, step_fn, st_sh = build(cfg, mesh, shape, lr=lr,
                                      compress_grads=compress_grads)
        start = 0
        if ckpt and resume and ckpt.latest_step() is not None:
            start, state = ckpt.restore(shardings=st_sh)
            print(f"resumed from step {start}")
        t0 = time.perf_counter()
        step_fn = step_fn.lower(state, api.input_specs(cfg, shape)).compile()
        compile_s = time.perf_counter() - t0

        pipe = TokenPipeline(cfg, shape, PipelineConfig(prefetch=2))
        pipe.start(from_step=start)
        losses, step_s = [], []
        try:
            with (jax.profiler.trace(profile) if profile
                  else contextlib.nullcontext()):
                for step in range(start, steps):
                    batch = pipe.get()
                    t0 = time.perf_counter()
                    with jax.profiler.StepTraceAnnotation("train",
                                                          step_num=step):
                        state, metrics = step_fn(state, batch)
                        jax.block_until_ready((state, metrics))
                    step_s.append(time.perf_counter() - t0)
                    losses.append(metrics["loss"])
                    if log_every and (step % log_every == 0 or
                                      step == steps - 1):
                        print(json.dumps({
                            "step": step, "loss": round(float(losses[-1]), 4),
                            "grad_norm": round(float(metrics["grad_norm"]), 3),
                            "step_s": step_s[-1]}))
                    if ckpt and (step + 1) % ckpt_every == 0:
                        ckpt.save(step + 1, state)
        finally:
            pipe.stop()
    losses = [float(x) for x in losses]
    med = statistics.median(step_s)
    return state, {
        "initial_loss": losses[0], "final_loss": losses[-1],
        "improved": losses[-1] < losses[0], "losses": losses,
        "compile_s": compile_s, "step_s": step_s, "median_step_s": med,
        "tok_per_s": shape.tokens / med}


def main(argv=None):
    ap = argparse.ArgumentParser()
    add_model_args(ap, "llama3-8b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="trace the timed steps into DIR")
    args = ap.parse_args(argv)

    init_compile_cache()
    cfg = model_config(args.arch, tiny=args.reduced, layers=args.layers)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_device_mesh(data=len(jax.devices()))
    ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    state, out = train(cfg, mesh, shape, steps=args.steps, lr=args.lr,
                       compress_grads=args.compress_grads, ckpt=ckpt,
                       ckpt_every=args.ckpt_every, resume=args.resume,
                       log_every=args.log_every, profile=args.profile)
    if ckpt:
        ckpt.save(args.steps, state)
        ckpt.wait()
    print(json.dumps({k: out[k] for k in
                      ("final_loss", "initial_loss", "improved", "compile_s",
                       "median_step_s", "tok_per_s")}))
    return 0 if out["improved"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
