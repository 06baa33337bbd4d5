"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the jit-ed step (train_step for train shapes,
prefill_step / serve_step for inference shapes), attaches the sharding
plan, lowers with ShapeDtypeStruct stand-ins (no allocation), compiles,
and records memory_analysis / cost_analysis / per-collective bytes for
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k \
      [--multi-pod] [--out results.json] [--plan default]
  python -m repro.launch.dryrun --all [--out dir/]
"""

import argparse
import json
import os
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from ..analysis.hlo_stats import analyze as analyze_hlo
from ..analysis.roofline import (adjusted_terms, roofline_terms,
                                 summarize_memory)
from ..distributed import hints
from ..configs.base import SHAPES, get, registry
from ..distributed import sharding as shard
from ..models import api
from ..optim.adamw import AdamWConfig
from ..train.step import (init_train_state, make_prefill_step,
                          make_serve_step, make_train_step)
from .common import init_compile_cache
from .mesh import make_production_mesh

REPLICATED = None  # alias for readability


def cell_applicable(arch: str, shape_name: str) -> bool:
    cfg = get(arch)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False   # pure full-attention archs skip (DESIGN.md §5)
    return True


def build_cell(arch: str, shape_name: str, mesh, plan: str = "default"):
    """Returns (jitted_fn, example_args_with_shardings)."""
    cfg = get(arch)
    shape = SHAPES[shape_name]
    specs = api.input_specs(cfg, shape)

    if shape.kind == "train":
        step = make_train_step(cfg, AdamWConfig())
        state_abs = jax.eval_shape(
            partial(init_train_state, cfg=cfg), jax.random.PRNGKey(0))
        st_specs = shard.state_specs(state_abs, cfg, mesh, plan)
        b_specs = shard.batch_specs(specs, cfg, mesh, plan)
        state_in = shard.with_sharding(state_abs, st_specs, mesh)
        batch_in = shard.with_sharding(specs, b_specs, mesh)
        jitted = jax.jit(
            step,
            in_shardings=(shard.to_named(st_specs, mesh),
                          shard.to_named(b_specs, mesh)),
            out_shardings=(shard.to_named(st_specs, mesh), REPLICATED),
            donate_argnums=(0,))
        return jitted, (state_in, batch_in)

    params_abs = jax.eval_shape(
        partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    p_specs = shard.params_specs(params_abs, cfg, mesh, plan)
    params_in = shard.with_sharding(params_abs, p_specs, mesh)

    if shape.kind == "prefill":
        b_specs = shard.batch_specs(specs, cfg, mesh)
        batch_in = shard.with_sharding(specs, b_specs, mesh)
        jitted = jax.jit(
            make_prefill_step(cfg),
            in_shardings=(shard.to_named(p_specs, mesh),
                          shard.to_named(b_specs, mesh)))
        return jitted, (params_in, batch_in)

    # decode: one new token against a cache of seq_len
    B, S = shape.global_batch, shape.seq_len
    cache_abs = jax.eval_shape(partial(api.init_cache, cfg, B, S))
    c_specs = shard.cache_specs(cache_abs, cfg, mesh)
    cache_in = shard.with_sharding(cache_abs, c_specs, mesh)
    tok_abs = specs["token"]
    t_spec = shard.batch_specs({"token": tok_abs}, cfg, mesh)["token"]
    tok_in = shard.with_sharding({"token": tok_abs},
                                 {"token": t_spec}, mesh)["token"]
    pos_in = jax.ShapeDtypeStruct((), jnp.int32)
    serve = make_serve_step(cfg)
    jitted = jax.jit(
        serve,
        in_shardings=(shard.to_named(p_specs, mesh),
                      shard.to_named(c_specs, mesh),
                      shard.to_named({"t": t_spec}, mesh)["t"], REPLICATED),
        out_shardings=(REPLICATED, shard.to_named(c_specs, mesh)),
        donate_argnums=(1,))
    return jitted, (params_in, cache_in, tok_in, pos_in)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             plan: str = "default") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    hints.set_mesh(mesh)
    hints.set_plan(plan)
    t0 = time.time()
    try:
        jitted, args = build_cell(arch, shape_name, mesh, plan)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost_raw = compiled.cost_analysis()
            hlo = compiled.as_text()
    finally:
        hints.set_mesh(None)
        hints.set_plan("default")
    stats = analyze_hlo(hlo)
    # analyzer numbers are per-device with while-trip multiplication
    # (cost_analysis counts loop bodies once — see EXPERIMENTS.md §Dry-run)
    cost = {"flops": stats["flops"], "bytes accessed": stats["bytes"]}
    coll = dict(stats["collectives"])
    cfg = get(arch)
    shape = SHAPES[shape_name]
    terms = roofline_terms(cost, coll, chips=chips, cfg=cfg, shape=shape)
    terms.update(adjusted_terms(terms, stats.get("tag_bytes", {}), cfg,
                                shape, chips))
    out = {
        "arch": arch, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "chips": chips,
        "plan": plan,
        "memory": summarize_memory(mem),
        "cost": cost,
        "cost_raw_xla": {k: cost_raw.get(k, 0.0) for k in
                         ("flops", "bytes accessed")},
        "collectives": coll,
        "hlo_computations": stats["num_computations"],
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "status": "ok",
        "roofline": terms,
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--plan", default="default")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    # the production meshes need 512 devices: host devices, set before JAX
    # starts its backend
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        "--xla_force_host_platform_device_count=512",
        os.environ.get("XLA_FLAGS")]))
    init_compile_cache()
    cells = []
    if args.all:
        for arch in sorted(registry()):
            for shape in SHAPES:
                if cell_applicable(arch, shape):
                    cells.append((arch, shape))
    else:
        if not cell_applicable(args.arch, args.shape):
            print(json.dumps({"arch": args.arch, "shape": args.shape,
                              "status": "skipped",
                              "reason": "full-attention arch at 500k "
                                        "(DESIGN.md §5)"}))
            return 0
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        try:
            r = run_cell(arch, shape, args.multi_pod, args.plan)
        except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
            r = {"arch": arch, "shape": shape, "status": "error",
                 "error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc()[-2000:]}
        results.append(r)
        print(json.dumps(r if r["status"] != "error" else
                         {k: r[k] for k in ("arch", "shape", "status",
                                            "error")}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"] == "error"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
